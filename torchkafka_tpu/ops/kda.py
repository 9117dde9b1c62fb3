"""Kimi Delta Attention (KDA, arXiv:2510.26692): the delta rule with a decay
a CHANNEL of a head, as one decode step over a slot's recurrent state and
as the chunkwise form an admission runs over a whole prompt.

A head keeps a state ``S`` [d_k, d_v] (float32). A token brings unit-norm
``q, k`` [d_k], ``v`` [d_v], a log-decay ``g`` [d_k] (<= 0, one a channel:
the "fine-grained diagonal gating") and a scalar ``beta`` in (0, 1):

    S' = Diag(exp(g)) S            the decay, a row of S a channel of k
    u  = v - S'^T k                what the state does not yet say of k
    S  = S' + beta k u^T           the rank-one correction (delta rule)
    o  = S^T q / sqrt(d_k)         the read-out

**The step** (``kda_step``) passes over a layer's state ONCE: decay,
correction and read-out of a (slot, head) on one 128 x 128 tile held in
VMEM, the state ``[layers, slots, heads, d_k, d_v]`` aliased in place
(``tk_kda_step``; the pool stays where it lies, as ``tk_kvattn_dynlen``
carries its pools). The products are the vector unit's: a head's state
meets ONE vector a product, which fills a 128th of a matrix unit. Off the
TPU the same arithmetic runs as ``jax.numpy`` (``kda_step_xla``; the
tests run the kernel under the Pallas interpreter against it).

**The chunkwise form** (``kda_chunk``) does not walk a prompt token by
token. In a chunk of C tokens from a state ``S0``, with ``G_r`` the
running sum of ``g`` and ``w_r = beta_r u_r``:

    (I + Diag(beta) tril(A, -1)) W = Diag(beta) (V - K+ S0)
        A[r, s] = sum_c k_r[c] k_s[c] exp(G_r[c] - G_s[c]),  K+_r = k_r exp(G_r)
    O  = (Q+ S0 + tril(B) W) / sqrt(d_k),  B[r, s] likewise of q_r and k_s
    S  = Diag(exp(G_C)) S0 + (K exp(G_C - G))^T W

so the triangular system is solved for ``[V | K+]`` of every chunk at
once and only three products a chunk ride the scan that carries ``S``.
``exp(G_r - G_s)`` is formed as a product of two factors about the start
of r's SUB-chunk of 16 tokens: with ``g >= -5`` neither leaves float32's
exponent (16 x 5 = 80 < 88), where ``exp(-G_s)`` over a chunk of 64 would
(320).
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

CHUNK = 64
SUB_CHUNK = 16  # times |g| <= 5: the decay's range inside float32's exponent
# Heads of one slot a grid step of the step kernel takes: 32 tiles of
# 64 KiB in and out, double-buffered, are 8 MiB of the default scoped
# VMEM. Read on the v5e at 384 slots of 32 heads, ms a call (PERF.md §6,
# PR 41): 8 heads 3.67, 16 2.98, 32 2.70; the jax.numpy step 4.88.
STEP_HEADS = 32
_HI = lax.Precision.HIGHEST


def gate(a, a_log, dt_bias, lower_bound: float):
    """The safe gate: ``g = lower_bound * sigmoid(exp(A_log_h) * (a +
    dt_bias))``, in ``(lower_bound, 0)``. a [..., H, d_k] → float32."""
    a = a.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    return jnp.float32(lower_bound) * jax.nn.sigmoid(rate * a)


def l2_norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def short_conv(x, taps):
    """Causal depthwise convolution over the last ``len(taps)`` tokens,
    then SiLU. x [B, S, C] with S the tokens in order (the caller puts a
    slot's conv tail in front of a decode token); taps [T, C], the last
    the current token's → [B, S - T + 1, C] float32."""
    t = taps.shape[0]
    n = x.shape[1] - t + 1
    x, taps = x.astype(jnp.float32), taps.astype(jnp.float32)
    y = sum(x[:, i:i + n] * taps[i] for i in range(t))
    return jax.nn.silu(y)


# ------------------------------------------------------------------ the step


def kda_step_xla(state, layer, q, k, v, g, beta):
    """One token a slot through layer ``layer`` of the stacked state
    [L, B, H, d_k, d_v] float32: (o [B, H, d_v] float32, the state with
    the layer's slab replaced). q, k, v, g [B, H, d] float32, beta
    [B, H]."""
    s = lax.dynamic_index_in_dim(state, layer, keepdims=False)
    s = s * jnp.exp(g)[..., None]
    u = v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    s = s + (beta[..., None] * k)[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    state = lax.dynamic_update_index_in_dim(state, s, layer, 0)
    return o * jnp.float32(1.0 / math.sqrt(q.shape[-1])), state


def _step_kernel(base_ref, q_ref, k_ref, kb_ref, a_ref, v_ref, s_ref,
                 o_ref, s_out_ref, *, heads: int):
    """A slot's ``heads`` heads: each tile is read once, decayed,
    corrected, read out and written once. The vectors that scale the
    tile's ROWS (q, k, beta k, exp(g)) come channel-major, [d_k, heads]:
    a head's is a column, broadcast along the lanes; v and o are rows."""
    del base_ref
    for h in range(heads):
        col = (slice(None), slice(h, h + 1))
        s = s_ref[0, 0, h] * a_ref[0, 0][col]
        u = v_ref[0, 0, h:h + 1, :] - jnp.sum(
            s * k_ref[0, 0][col], axis=0, keepdims=True
        )
        s = s + kb_ref[0, 0][col] * u
        s_out_ref[0, 0, h] = s
        o_ref[0, 0, h:h + 1, :] = jnp.sum(
            s * q_ref[0, 0][col], axis=0, keepdims=True
        )


def kda_step(state, layer, q, k, v, g, beta, *, interpret: bool = False):
    """``kda_step_xla`` as the Pallas kernel ``tk_kda_step``: the state
    comes back aliased to the one passed in, the other layers' slabs
    untouched."""
    _nl, b, h, dk, dv = state.shape
    hb = math.gcd(h, STEP_HEADS)
    scale = jnp.float32(1.0 / math.sqrt(dk))

    def cols(x):  # [B, H, d_k] -> [B, H / hb, d_k, hb]
        return x.reshape(b, h // hb, hb, dk).swapaxes(2, 3)

    operands = (
        cols(q * scale), cols(k), cols(k * beta[..., None]),
        cols(jnp.exp(g)), v.reshape(b, h // hb, hb, dv),
    )
    col_spec = pl.BlockSpec((1, 1, dk, hb), lambda i, j, base: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, 1, hb, dv), lambda i, j, base: (i, j, 0, 0))
    tile_spec = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda i, j, base: (base[0], i, j, 0, 0)
    )
    kw = {} if interpret else tpu_compiler_params(("parallel", "parallel"))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // hb),
            in_specs=[col_spec] * 4 + [row_spec, tile_spec],
            out_specs=[row_spec, tile_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h // hb, hb, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        # Operand numbers count the scalar-prefetch argument.
        input_output_aliases={6: 1},
        interpret=interpret,
        name="tk_kda_step",
        **kw,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *operands, state)
    return o.reshape(b, h, dv), state


# ------------------------------------------------------------ the chunk form


def kda_chunk(q, k, v, g, beta, state=None):
    """A whole sequence from ``state`` (None: zero): q, k, v, g
    [B, S, H, d] float32 (q, k unit-norm), beta [B, S, H] → (o [B, S, H,
    d_v] float32, the state after the last token [B, H, d_k, d_v])."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    sub = SUB_CHUNK
    c = min(CHUNK, -(-s // sub) * sub)
    pad = -s % c
    if pad:
        # A padding token decays nothing (g 0) and corrects nothing (beta,
        # k 0): the state after it is the state before it.
        q, k, v, g = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g)
        )
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n, a = (s + pad) // c, c // sub

    def chunks(x):  # [B, S, H, d] -> [B, H, N, C, d]
        return x.reshape(b, n, c, h, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g = (chunks(x.astype(jnp.float32)) for x in (q, k, v, g))
    beta = chunks(beta.astype(jnp.float32)[..., None])  # [B, H, N, C, 1]
    gc = jnp.cumsum(g, axis=3)  # G_r, the chunk's own running sum
    # G at the start of each token's sub-chunk: the sum up to the token
    # before it, 0 for the first sub-chunk.
    starts = jnp.concatenate([
        jnp.zeros_like(gc[..., :1, :]), gc[..., sub - 1:c - 1:sub, :]
    ], axis=3)  # [B, H, N, A, d]
    to_start = jnp.exp(gc - jnp.repeat(starts, sub, axis=3))  # in [e-80, 1]
    # A column s as sub-chunk a's rows see it: k_s exp(G_start(a) - G_s),
    # for the tokens up to a's end alone (beyond them the exponent is
    # positive without bound, and the causal mask drops them anyway).
    expo = starts[..., :, None, :] - gc[..., None, :, :]  # [.., A, C, d]
    seen = jnp.arange(c)[None, :] < (jnp.arange(a)[:, None] + 1) * sub
    cols = k[..., None, :, :] * jnp.exp(
        jnp.where(seen[..., None], expo, -jnp.inf)
    )

    def against_cols(rows):  # [.., C, d] -> [.., C, C]
        rows = (rows * to_start).reshape(b, h, n, a, sub, dk)
        out = jnp.einsum("bhnard,bhnasd->bhnars", rows, cols, precision=_HI)
        return out.reshape(b, h, n, c, c)

    lower = jnp.tril(jnp.ones((c, c), bool))
    kk = jnp.where(lower & ~jnp.eye(c, dtype=bool), against_cols(k), 0.0)
    qk = jnp.where(lower, against_cols(q), 0.0)
    decayed = jnp.exp(gc)
    solved = jax.scipy.linalg.solve_triangular(
        jnp.eye(c, dtype=jnp.float32) + beta * kk,
        beta * jnp.concatenate([v, k * decayed], axis=-1),
        lower=True, unit_diagonal=True,
    )
    wv, wk = solved[..., :dv], solved[..., dv:]
    total = gc[..., -1:, :]  # G_C
    k_end = k * jnp.exp(total - gc)
    q_in = q * decayed
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)

    def one(s0, xs):
        wv, wk, qk, q_in, k_end, total = xs
        w = wv - jnp.einsum("bhck,bhkv->bhcv", wk, s0, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_in, s0, precision=_HI)
        o = o + jnp.einsum("bhcs,bhsv->bhcv", qk, w, precision=_HI)
        s1 = jnp.exp(total).swapaxes(-1, -2) * s0 + jnp.einsum(
            "bhck,bhcv->bhkv", k_end, w, precision=_HI
        )
        return s1, o

    state, o = lax.scan(one, state.astype(jnp.float32), tuple(
        jnp.moveaxis(x, 2, 0) for x in (wv, wk, qk, q_in, k_end, total)
    ))
    o = jnp.moveaxis(o, 0, 2)  # [B, H, N, C, d_v]
    o = o.transpose(0, 2, 3, 1, 4).reshape(b, n * c, h, dv)[:, :s]
    return o * jnp.float32(1.0 / math.sqrt(dk)), state
