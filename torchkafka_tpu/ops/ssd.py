"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060): a scalar
decay a HEAD, as one decode step over a slot's state and as the chunked
scan an admission runs over a whole prompt.

A head keeps a state ``S`` [P, N] (float32; P the head's width, N the
state's). A token brings ``x`` [P], a step ``dt`` > 0 (one a head, after
its softplus), and ``B``, ``C`` [N], which every head of the layer SHARES
(one group); the head has a rate ``A`` < 0 and a skip weight ``D``:

    S = exp(dt A) S + (dt x) B^T     the decay, one scalar for the whole tile
    y = S C + D x                    the read-out and the skip

so, unlike the delta rule (``ops/kda.py``), nothing is read back from the
state before it is written: no rank-one correction.

**The step** (``ssd_step``) passes over a layer's state ONCE: decay, outer
product, read-out and skip of ``STEP_HEADS`` heads of a slot a grid step,
the state ``[layers, slots, heads, P, N]`` aliased in place
(``tk_ssd_step``). ``dt = 0`` is a slot that is not active: the decay is
exactly 1 and the outer product exactly 0, so the kernel writes back what
it read, bit for bit. Off the TPU the same arithmetic runs as
``jax.numpy`` (``ssd_step_xla``; the tests run the kernel under the Pallas
interpreter against it).

**The chunked scan** (``ssd_chunk``) does not walk a prompt token by
token. In a chunk of Q tokens from a state ``S0``, with ``G_r`` the running
sum of ``dt A``:

    Y_r = sum_{s <= r} (C_r . B_s) exp(G_r - G_s) dt_s x_s  +  exp(G_r) S0 C_r
    S   = exp(G_Q) S0 + sum_s exp(G_Q - G_s) (dt_s x_s) B_s^T

``C_r . B_s`` is one [Q, Q] product a chunk for all the heads; every
exponent is of a sum over s < j <= r of non-positive terms, formed as a
difference under the causal mask, so nothing leaves float32. The chunks
ride one ``lax.scan`` that carries ``S``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchkafka_tpu.ops.flash import tpu_compiler_params

CHUNK = 256
# Heads of one slot a grid step of the step kernel takes: 64 tiles of
# 32 KiB in and out, double-buffered, are 8 MiB of the default scoped
# VMEM. Read on the v5e at 128 slots of 128 heads, us a call (PERF.md §6,
# PR 45): 32 heads 2,014, 64 1,968, 128 1,959: the block's size is not
# what holds the kernel at two thirds of the HBM peak.
STEP_HEADS = 64
_HI = lax.Precision.HIGHEST


def short_conv(x, taps, bias):
    """Causal depthwise convolution over the last ``len(taps)`` tokens
    with a bias a channel, then SiLU. x [B, S, C] with S the tokens in
    order (the caller puts a slot's conv tail in front of a decode token);
    taps [T, C], the last the current token's → [B, S - T + 1, C]
    float32."""
    t = taps.shape[0]
    n = x.shape[1] - t + 1
    x, taps = x.astype(jnp.float32), taps.astype(jnp.float32)
    y = sum(x[:, i:i + n] * taps[i] for i in range(t))
    return jax.nn.silu(y + bias.astype(jnp.float32))


def conv_step(tail, new, taps, bias):
    """``short_conv`` for ONE token a slot over a tail kept in one row:
    tail [B, (T - 1) * C] (the last T - 1 tokens' rows side by side), new
    [B, C] → (the convolution's output [B, C] float32, the tail with the
    oldest row dropped and ``new`` behind). Every operand is a dense [B,
    C] tile: as [B, T, C] the rows sit on an axis of 4 that the device
    pads, and each is re-laid to be concatenated."""
    c = new.shape[-1]
    t = taps.shape[0]
    taps = taps.astype(jnp.float32)
    rows = [tail[:, i * c:(i + 1) * c] for i in range(t - 1)] + [new]
    y = sum(r.astype(jnp.float32) * taps[i] for i, r in enumerate(rows))
    fresh = jnp.concatenate([tail[:, c:], new.astype(tail.dtype)], axis=-1)
    return jax.nn.silu(y + bias.astype(jnp.float32)), fresh


# ------------------------------------------------------------------ the step


def ssd_step_xla(state, layer, x, dt, a, bm, cm, d):
    """One token a slot through layer ``layer`` of the stacked state
    [L, B, H, P, N] float32: (y [B, H, P] float32, the state with the
    layer's slab replaced). x [B, H, P], dt [B, H] (0: the slot keeps its
    state), a and d [H], bm and cm [B, N], all float32."""
    s = lax.dynamic_index_in_dim(state, layer, keepdims=False)
    s = s * jnp.exp(dt * a)[..., None, None] + (
        (dt[..., None] * x)[..., None] * bm[:, None, None, :]
    )
    y = jnp.einsum("bhpn,bn->bhp", s, cm, precision=_HI) + d[:, None] * x
    return y, lax.dynamic_update_index_in_dim(state, s, layer, 0)


def _step_kernel(base_ref, decay_ref, dtx_ref, dx_ref, b_ref, c_ref, s_ref,
                 y_ref, s_out_ref, *, heads: int):
    """A slot's ``heads`` heads: each tile is read once, decayed, given
    its outer product, read out and written once. What scales the tile's
    ROWS (the decay, ``dt x``, ``D x``) comes channel-major, [P, heads]: a
    head's is a column, broadcast along the lanes, and so is its read-out;
    B and C are rows, the same for every head."""
    del base_ref
    b, c = b_ref[0], c_ref[0]  # [1, N]
    for h in range(heads):
        col = (slice(None), slice(h, h + 1))
        s = s_ref[0, 0, h] * decay_ref[0, 0][col] + dtx_ref[0, 0][col] * b
        s_out_ref[0, 0, h] = s
        y_ref[0, 0, :, h:h + 1] = (
            jnp.sum(s * c, axis=1, keepdims=True) + dx_ref[0, 0][col]
        )


def ssd_step(state, layer, x, dt, a, bm, cm, d, *, interpret: bool = False):
    """``ssd_step_xla`` as the Pallas kernel ``tk_ssd_step``: the state
    comes back aliased to the one passed in, the other layers' slabs
    untouched."""
    _nl, b, h, p, n = state.shape
    hb = math.gcd(h, STEP_HEADS)

    def cols(v):  # [B, H, P] -> [B, H / hb, P, hb]
        return v.reshape(b, h // hb, hb, p).swapaxes(2, 3)

    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    operands = (
        cols(decay), cols(dt[..., None] * x), cols(d[:, None] * x),
        bm[:, None, :], cm[:, None, :],
    )
    col_spec = pl.BlockSpec((1, 1, p, hb), lambda i, j, base: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, 1, n), lambda i, j, base: (i, 0, 0))
    tile_spec = pl.BlockSpec(
        (1, 1, hb, p, n), lambda i, j, base: (base[0], i, j, 0, 0)
    )
    kw = {} if interpret else tpu_compiler_params(("parallel", "parallel"))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // hb),
            in_specs=[col_spec] * 3 + [row_spec] * 2 + [tile_spec],
            out_specs=[col_spec, tile_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h // hb, p, hb), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        # Operand numbers count the scalar-prefetch argument.
        input_output_aliases={6: 1},
        interpret=interpret,
        name="tk_ssd_step",
        **kw,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *operands, state)
    return y.swapaxes(2, 3).reshape(b, h, p), state


# ------------------------------------------------------------ the chunk form


def ssd_chunk(x, dt, a, bm, cm, d, state=None, chunk: int = CHUNK):
    """A whole sequence from ``state`` (None: zero): x [B, S, H, P], dt
    [B, S, H], bm and cm [B, S, N], a and d [H], float32 → (y [B, S, H, P]
    float32, the state after the last token [B, H, P, N])."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, -(-s // 8) * 8)
    pad = -s % q
    if pad:
        # A padding token takes no step (dt 0): the state after it is the
        # state before it.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm, cm = (jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in (bm, cm))
    nq = (s + pad) // q

    def chunks(v):  # [B, S, ...] -> [N, B, Q, ...]
        return jnp.moveaxis(v.reshape(b, nq, q, *v.shape[2:]), 1, 0)

    x, dt, bm, cm = (chunks(v.astype(jnp.float32)) for v in (x, dt, bm, cm))
    a, d = a.astype(jnp.float32), d.astype(jnp.float32)
    lower = jnp.tril(jnp.ones((q, q), bool))
    if state is None:
        state = jnp.zeros((b, h, p, n), jnp.float32)

    def one(s0, xs):
        x, dt, bm, cm = xs  # [B, Q, H, P], [B, Q, H], [B, Q, N] twice
        g = jnp.cumsum(dt * a, axis=1)  # G_r [B, Q, H]
        dtx = dt[..., None] * x
        # exp(G_r - G_s) for s <= r, a head: [B, H, Q, Q].
        span = g.transpose(0, 2, 1)[..., :, None] - (
            g.transpose(0, 2, 1)[..., None, :]
        )
        decay = jnp.exp(jnp.where(lower, span, -jnp.inf))
        cb = jnp.einsum("brn,bsn->brs", cm, bm, precision=_HI)
        y = jnp.einsum(
            "bhrs,bshp->brhp", cb[:, None] * decay, dtx, precision=_HI
        )
        y = y + jnp.exp(g)[..., None] * jnp.einsum(
            "bhpn,brn->brhp", s0, cm, precision=_HI
        )
        to_end = jnp.exp(g[:, -1:] - g)  # exp(G_Q - G_s) [B, Q, H]
        s1 = jnp.exp(g[:, -1])[..., None, None] * s0 + jnp.einsum(
            "bshp,bsn->bhpn", to_end[..., None] * dtx, bm, precision=_HI
        )
        return s1, y + d[:, None] * x

    state, y = lax.scan(one, state.astype(jnp.float32), (x, dt, bm, cm))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nq * q, h, p)[:, :s]
    return y, state
