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

**The step** (``ssd_step``) passes over a layer's state ONCE, the state
``[layers, slots, heads, P, N]`` aliased in place (``tk_ssd_step``),
``STEP_HEADS`` heads of a slot a grid step. The update is the vector
unit's, in float32, operation for operation: a head's decay is a SCALAR
(SMEM; no broadcast), ``dt x`` a column broadcast along the lanes, B a
row. The read-out is the matrix unit's: C against the tiles of
``STEP_GROUP`` heads as rows ``[group * P, N]``, both contracted on
their lanes at ``Precision.HIGHEST``, so it leaves as a dense ROW of
``y`` laid ``[B, H * P]``: no lane reduction to a column, no masked
column store, no transpose of ``y`` after (PERF.md §6, PR 46, has what
each of those cost). The skip ``D x`` is added outside, where it fuses
with what reads ``y``. ``dt = 0`` is a slot that is not active: the
decay is exactly 1 and the outer product exactly 0, so the kernel writes
back what it read, bit for bit. Off the TPU the same arithmetic runs as
``jax.numpy`` (``ssd_step_xla``; the tests run the kernel under the
Pallas interpreter against it).

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
# VMEM. The kernel is held by its DMA and by nothing else: read on the v5e
# at 128 slots of 128 heads (PERF.md §6, PR 46), 32 / 64 / 128 heads a
# grid step are 1,690 / 1,671 / 1,661 us a call, where the same blocks
# streamed through a bare multiply, no column and no read-out, take 1,684
# (640 GB/s in and out in place: 78% of the HBM peak is what such a stream
# gets). Before PR 46 a tile paid two column broadcasts, a lane reduction
# and a masked column store: 1,985 us, and 165 us less for each cross-lane
# operation a vreg taken out, down to that floor.
STEP_HEADS = 64
# Heads whose tiles one read-out product takes: 8 heads of 64 channels are
# 512 rows of the state against C (2, 8 and 16 heads read the same time).
STEP_GROUP = 8
_HI = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # both operands contracted on their lanes


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


def _step_kernel(base_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref, y_ref,
                 s_out_ref, *, heads: int, group: int):
    """A slot's ``heads`` heads: each tile is read once, decayed, given
    its outer product and written once on the vector unit; the tiles of
    ``group`` heads, as rows [group * P, N], are then read out by ONE
    product with C that contracts the lanes and leaves as a dense row of
    ``y``. A head's decay is a scalar (SMEM); ``dt x`` scales the tile's
    ROWS and comes channel-major, [P, heads], a head's a column broadcast
    along the lanes; B and C are rows, the same for every head."""
    del base_ref
    p, n = s_ref.shape[3:]
    b = b_ref[0]  # [1, N]
    c = jnp.broadcast_to(c_ref[0], (8, n))  # a product's least 8 rows
    rows = group * p
    for g in range(heads // group):
        for h in range(g * group, (g + 1) * group):
            s_out_ref[0, 0, h] = (
                s_ref[0, 0, h] * decay_ref[0, 0, 0, h]
                + dtx_ref[0, 0][:, h:h + 1] * b
            )
        tiles = s_out_ref[0, 0, g * group:(g + 1) * group].reshape(rows, n)
        y = lax.dot_general(
            c, tiles, _NT, precision=_HI, preferred_element_type=jnp.float32
        )
        y_ref[0, 0, :, g * rows:(g + 1) * rows] = y[:1]


def ssd_step(state, layer, x, dt, a, bm, cm, d, *, interpret: bool = False):
    """``ssd_step_xla`` as the Pallas kernel ``tk_ssd_step``: the state
    comes back aliased to the one passed in, the other layers' slabs
    untouched. The kernel returns ``S C`` as rows [B, H * P]; the skip
    ``D x`` is added here, where it fuses with what reads ``y``."""
    _nl, b, h, p, n = state.shape
    hb = math.gcd(h, STEP_HEADS)
    blocks = h // hb
    dtx = (dt[..., None] * x).reshape(b, blocks, hb, p).swapaxes(2, 3)
    decay_spec = pl.BlockSpec(
        (1, 1, 1, hb), lambda i, j, base: (i, j, 0, 0),
        memory_space=pltpu.SMEM,
    )
    col_spec = pl.BlockSpec((1, 1, p, hb), lambda i, j, base: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, 1, n), lambda i, j, base: (i, 0, 0))
    y_spec = pl.BlockSpec((1, 1, 1, hb * p), lambda i, j, base: (i, j, 0, 0))
    tile_spec = pl.BlockSpec(
        (1, 1, hb, p, n), lambda i, j, base: (base[0], i, j, 0, 0)
    )
    kw = {} if interpret else tpu_compiler_params(("parallel", "parallel"))
    y, state = pl.pallas_call(
        functools.partial(
            _step_kernel, heads=hb, group=math.gcd(hb, STEP_GROUP)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, blocks),
            in_specs=[decay_spec, col_spec, row_spec, row_spec, tile_spec],
            out_specs=[y_spec, tile_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, blocks, 1, hb * p), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        # Operand numbers count the scalar-prefetch argument.
        input_output_aliases={5: 1},
        interpret=interpret,
        name="tk_ssd_step",
        **kw,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.exp(dt * a).reshape(b, blocks, 1, hb), dtx,
        bm[:, None, :], cm[:, None, :], state,
    )
    return y.reshape(b, h, p) + d[:, None] * x, state


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
