"""The linear layers of a hybrid model beside its attention layers: a
layer of either kind over a whole sequence and as one decode token a
slot, and the forward that keeps what serving holds of it.

``TransformerConfig.linear_pattern`` says which layers of a period are
linear (True) and which are attention layers (False); the leading dense
layers (``first_dense_layers``) are linear. Two properties of the config
say what runs in them, each apart from the other: ``linear_kind`` the
RECURRENCE of a linear layer ("kda", the delta rule with a decay a
channel, ``ops/kda.py``; "ssd", the Mamba-2 mixer with a scalar decay a
head, ``ops/ssd.py``; "conv", the gated short convolution, which recurs
over nothing, ``ops/gconv.py``), and ``kv_lora_rank`` the ATTENTION of the
others
(latent, ``models/mla.py``, over one pool of latent rows; or, at 0,
grouped-query attention over pools of K and V rows, the layer the dense
paths have: ``Transformer._gqa_qkv`` here, ``slot_pool._slot_layer_step``
in a tick). A group's tensors are stacked BY KIND
(``transformer.scan_hybrid``): the norms and the MLP's over every layer
of the group, a linear layer's own over the group's linear layers, an
attention layer's over its attention layers.

A KDA layer's tensors, with H heads of E = ``linear_head_dim``:
``lqkv`` [D, 3 * H * E] (q, k and v before the convolution, side by
side), ``lconv`` [taps, 3 * H * E] (a causal depthwise convolution over
the last ``linear_conv`` tokens, its own taps a channel, then SiLU),
``lf`` [D, H, E], ``l_alog`` [H] and ``l_dt`` [H, E] (the decay a channel:
``ops/kda.py::gate``), ``lb`` [D, H] (beta), ``lg`` [D, H] (the output
gate, ONE scalar a head), ``lnorm`` [E] (RMSNorm of a head's read-out) and
``lo`` [H, E, D]. A latent layer has ``wg`` [D, H] too: the same
head-wise gate before ``wo`` (``attn_gate``).

An SSD layer's, with H = ``ssd_heads`` heads of P = ``ssd_head_dim`` and a
state of N = ``ssd_state_dim``: ``s_in`` [D, H * P + (H * P + 2 N)] (the
gate z and, before the convolution, x beside the one group's B and C),
``s_in_dt`` [D, H] (the step, with ``s_dt`` [H] under a softplus),
``s_conv`` [taps, H * P + 2 N] with its bias ``s_conv_b``, ``s_alog`` [H]
(the rate ``A = -exp(.)``), ``s_d`` [H] (the skip), ``s_norm`` [H * P]
(RMSNorm of ``y * SiLU(z)`` over all the heads' channels) and ``s_out``
[H, P, D].

A gated convolution's: ``g_in`` [D, 3 * D] (the gates b and c and the
value x, side by side), ``g_conv`` [taps, D] (no bias, no activation) and
``g_out`` [1, D, D].

What a slot keeps of a linear layer is NOT indexed by position: the
recurrent state in float32 (``[H, E, E]``; ``[H, P, N]``; the gated
convolution has none) and the conv tail, the last ``linear_conv - 1``
rows of what the convolution reads (what the next token's reads). These
are the layer's ``kept`` tensors, in this order, ``(state, tail)`` or
``(tail,)``: what a sequence leaves, what a step takes and returns, and
the head of the slot pool's tensors (``kept_tensors`` says how many). The
admission leaves them as they stand after the prompt window (the chunked
forms, ``kda_chunk``, ``ssd_chunk``; the convolution's shifted sum); a
tick updates the state in place, one pass (``tk_kda_step``,
``tk_ssd_step``), and rolls the tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from torchkafka_tpu.models import mla
from torchkafka_tpu.models.generate import _attn_tail_routing
from torchkafka_tpu.models.quant import load_weight
from torchkafka_tpu.models.transformer import (
    TransformerConfig,
    _rms_norm,
    hybrid_groups,
    scan_hybrid,
)
from torchkafka_tpu.ops import gconv, kda, ssd
from torchkafka_tpu.utils import tracing


def step_form() -> str:
    """How a tick passes over the state: the Pallas kernel on the TPU,
    ``jax.numpy`` elsewhere."""
    return "kernel" if jax.default_backend() == "tpu" else "xla"


def slot_shapes(cfg: TransformerConfig):
    """(a slot's state of one linear layer, its conv tail), by the
    recurrence's kind. The Mamba-2 mixer's tail is its ``taps - 1`` rows
    IN ONE ROW: as [taps - 1, C] the device pads the 3 rows to 4, and the
    compiler, short of memory, then keeps the tails "compressed" between
    their uses, a copy of every layer's tails there and back around each
    layer of each tick (14 copies, 10 ms a tick as compiled for a
    described v5e at 128 slots). The gated convolution keeps its tail the
    same way and has NO state: None."""
    taps = cfg.linear_conv - 1
    if cfg.linear_kind == "conv":
        return None, (taps * cfg.d_model,)
    if cfg.linear_kind == "ssd":
        return (
            (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state_dim),
            (taps * cfg.ssd_conv_dim,),
        )
    e = cfg.linear_head_dim
    return (cfg.n_heads, e, e), (taps, 3 * cfg.n_heads * e)


def kept_tensors(cfg: TransformerConfig) -> int:
    """How many tensors a slot keeps of a linear layer (module docstring)."""
    return sum(shape is not None for shape in slot_shapes(cfg))


def prefill_chunk(cfg: TransformerConfig) -> int | None:
    """Tokens a chunk of the admission's scan; None where it runs none
    (the convolution over a sequence is a sum of shifted copies)."""
    if cfg.linear_kind == "conv":
        return None
    return cfg.ssd_chunk if cfg.linear_kind == "ssd" else kda.CHUNK


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _project(h, layer, cfg: TransformerConfig):
    """Normed activations h [B, S, D] → (q, k, v before the convolution
    [B, S, 3 * H * E] in the compute dtype; g [B, S, H, E], beta and the
    output gate [B, S, H], float32)."""
    qkv = jnp.einsum("bsd,dc->bsc", h, load_weight(layer["lqkv"], cfg.dtype))
    a = jnp.einsum("bsd,dhe->bshe", h, load_weight(layer["lf"], cfg.dtype))
    g = kda.gate(a, layer["l_alog"], layer["l_dt"], cfg.linear_lower_bound)
    beta = _head_gate(h, layer["lb"], cfg)
    return qkv, g, beta, _head_gate(h, layer["lg"], cfg)


def _head_gate(h, w, cfg):
    return jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", h, load_weight(w, cfg.dtype),
        preferred_element_type=jnp.float32,
    ))


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _conv_qkv(rows, layer, cfg: TransformerConfig):
    """``rows`` [B, taps - 1 + S, 3 * H * E], the tokens before the S in
    front → unit-norm q and k, v [B, S, H, E] float32."""
    y = kda.short_conv(rows, layer["lconv"])
    b, s, _ = y.shape
    q, k, v = jnp.split(y.reshape(b, s, 3 * cfg.n_heads, -1), 3, axis=2)
    return kda.l2_norm(q), kda.l2_norm(k), v


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _finish(o, out_gate, layer, cfg: TransformerConfig):
    """A head's read-out [.., H, E] float32, RMS-normed over E with the
    learned weight, times the head's gate → compute dtype."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-6)
    o = o * layer["lnorm"].astype(jnp.float32) * out_gate[..., None]
    return o.astype(cfg.dtype)


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def gate_heads(attn, h, layer, cfg: TransformerConfig):
    """The latent layer's head-wise output gate (``attn_gate``): attn
    [B, S, H, v] times ``sigmoid(h · wg)`` a head."""
    if not cfg.attn_gate:
        return attn
    return attn * _head_gate(h, layer["wg"], cfg)[..., None].astype(attn.dtype)


def attend_sequence(h, layer, cfg: TransformerConfig):
    """A linear layer's mixer over a whole sequence from an empty state:
    normed h [B, S, D] → (the read-outs [B, S, H, E] before the output
    projection, ``out_projection``; the state after the last token,
    float32, or None where the kind has none; the conv tail as a slot
    keeps it, ``slot_shapes``)."""
    return _SEQUENCE[cfg.linear_kind](h, layer, cfg)


def out_projection(layer, cfg: TransformerConfig):
    """A linear layer's output projection, [H, E, D]."""
    return layer[_OUT[cfg.linear_kind]]


def attend_step(h, layer, cfg: TransformerConfig, kept, row, act):
    """One decode token a slot: normed h [B, 1, D] against row ``row`` of
    the stacked ``kept`` tensors (states [L, B, H, ., .] where the kind
    has one, conv tails, ``slot_shapes``) → (the read-out [B, 1, H, E],
    kept).
    ``act`` [B] bool or None: a slot that is not active keeps its state
    and its tail as they are (KDA: it decays nothing, g 0, and corrects
    nothing, beta 0; SSD: it takes no step, dt 0; the kernel writes back
    what it read; the convolution: its tail is not rolled)."""
    return _STEP[cfg.linear_kind](h, layer, cfg, *kept, row, act)


def _kda_sequence(h, layer, cfg: TransformerConfig):
    """→ (the gated read-outs [B, S, H, E], the state after the last token
    [B, H, E, E] float32, the conv tail [B, taps - 1, 3 * H * E])."""
    qkv, g, beta, out_gate = _project(h, layer, cfg)
    rows = jnp.pad(qkv, ((0, 0), (cfg.linear_conv - 1, 0), (0, 0)))
    q, k, v = _conv_qkv(rows, layer, cfg)
    with tracing.scope(tracing.SCOPE_ATTN_FLASH):
        o, state = kda.kda_chunk(q, k, v, g, beta)
    return (
        _finish(o, out_gate, layer, cfg), state,
        rows[:, rows.shape[1] - (cfg.linear_conv - 1):],
    )


def _kda_step(h, layer, cfg: TransformerConfig, states, tails, row, act):
    qkv, g, beta, out_gate = _project(h, layer, cfg)
    tail = lax.dynamic_index_in_dim(tails, row, keepdims=False)
    rows = jnp.concatenate([tail, qkv.astype(tail.dtype)], axis=1)
    q, k, v = _conv_qkv(rows, layer, cfg)
    g, beta, fresh = g[:, 0], beta[:, 0], rows[:, 1:]
    if act is not None:
        g = jnp.where(act[:, None, None], g, 0.0)
        beta = jnp.where(act[:, None], beta, 0.0)
        fresh = jnp.where(act[:, None, None], fresh, tail)
    with tracing.scope(tracing.SCOPE_KV_WRITE):
        tails = lax.dynamic_update_index_in_dim(tails, fresh, row, 0)
    with tracing.scope(tracing.SCOPE_KV_READ):
        step = kda.kda_step if step_form() == "kernel" else kda.kda_step_xla
        o, states = step(states, row, q[:, 0], k[:, 0], v[:, 0], g, beta)
    return _finish(o[:, None], out_gate, layer, cfg), (states, tails)


# ------------------------------------------------------- the Mamba-2 mixer


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _ssd_project(h, layer, cfg: TransformerConfig):
    """Normed h [B, S, D] → (the gate z [B, S, H * P] and, before the
    convolution, x beside B and C [B, S, H * P + 2 N], compute dtype; the
    step dt [B, S, H] float32, after its softplus)."""
    zx = jnp.einsum("bsd,dc->bsc", h, load_weight(layer["s_in"], cfg.dtype))
    dt = jnp.einsum(
        "bsd,dh->bsh", h, load_weight(layer["s_in_dt"], cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    dt = jax.nn.softplus(dt + layer["s_dt"].astype(jnp.float32))
    return zx[..., :cfg.ssd_inner], zx[..., cfg.ssd_inner:], dt


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _ssd_conv(rows, layer, cfg: TransformerConfig):
    """``rows`` [B, taps - 1 + S, H * P + 2 N], the tokens before the S in
    front → x [B, S, H, P], B and C [B, S, N], float32."""
    y = ssd.short_conv(rows, layer["s_conv"], layer["s_conv_b"])
    return _ssd_split(y, cfg)


def _ssd_split(y, cfg: TransformerConfig):
    """The convolution's output [..., H * P + 2 N] → x [..., H, P], B and
    C [..., N]."""
    inner, n = cfg.ssd_inner, cfg.ssd_state_dim
    x = y[..., :inner].reshape(*y.shape[:-1], cfg.ssd_heads, cfg.ssd_head_dim)
    return x, y[..., inner:inner + n], y[..., inner + n:]


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _ssd_finish(y, z, layer, cfg: TransformerConfig):
    """The read-outs y [B, S, H, P] float32 times SiLU(z), RMS-normed over
    ALL the heads' channels with the learned weight → compute dtype."""
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (y * layer["s_norm"].astype(jnp.float32)).astype(cfg.dtype)
    return y.reshape(*y.shape[:2], cfg.ssd_heads, cfg.ssd_head_dim)


def _ssd_rates(layer):
    """(A [H] < 0, D [H]), float32."""
    return (
        -jnp.exp(layer["s_alog"].astype(jnp.float32)),
        layer["s_d"].astype(jnp.float32),
    )


def _ssd_sequence(h, layer, cfg: TransformerConfig):
    z, xbc, dt = _ssd_project(h, layer, cfg)
    rows = jnp.pad(xbc, ((0, 0), (cfg.linear_conv - 1, 0), (0, 0)))
    x, bm, cm = _ssd_conv(rows, layer, cfg)
    a, d = _ssd_rates(layer)
    with tracing.scope(tracing.SCOPE_ATTN_FLASH):
        y, state = ssd.ssd_chunk(x, dt, a, bm, cm, d, chunk=cfg.ssd_chunk)
    tail = rows[:, rows.shape[1] - (cfg.linear_conv - 1):]
    return (
        _ssd_finish(y, z, layer, cfg), state,
        tail.reshape(tail.shape[0], -1),  # (``slot_shapes``)
    )


def _ssd_step(h, layer, cfg: TransformerConfig, states, tails, row, act):
    z, xbc, dt = _ssd_project(h, layer, cfg)
    tail = lax.dynamic_index_in_dim(tails, row, keepdims=False)
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        y, fresh = ssd.conv_step(
            tail, xbc[:, 0].astype(tail.dtype), layer["s_conv"],
            layer["s_conv_b"],
        )
        x, bm, cm = _ssd_split(y, cfg)
    dt = dt[:, 0]
    if act is not None:
        dt = jnp.where(act[:, None], dt, 0.0)
        fresh = jnp.where(act[:, None], fresh, tail)
    with tracing.scope(tracing.SCOPE_KV_WRITE):
        tails = lax.dynamic_update_index_in_dim(tails, fresh, row, 0)
    a, d = _ssd_rates(layer)
    with tracing.scope(tracing.SCOPE_KV_READ):
        step = ssd.ssd_step if step_form() == "kernel" else ssd.ssd_step_xla
        y, states = step(states, row, x, dt, a, bm, cm, d)
    return _ssd_finish(y[:, None], z, layer, cfg), (states, tails)


# ------------------------------------------------- the gated short convolution


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _conv_project(h, layer, cfg: TransformerConfig):
    """Normed h [B, S, D] → the gates b and c and the value x [B, S, D]."""
    bcx = jnp.einsum("bsd,dc->bsc", h, load_weight(layer["g_in"], cfg.dtype))
    return jnp.split(bcx, 3, axis=-1)


def _conv_sequence(h, layer, cfg: TransformerConfig):
    """→ (y [B, S, 1, D], no state, the tail [B, (taps - 1) * D])."""
    b, c, x = _conv_project(h, layer, cfg)
    with tracing.scope(tracing.SCOPE_ATTN_FLASH):
        y, tail = gconv.gconv_seq(b, c, x, layer["g_conv"])
    return y[:, :, None], None, tail


def _conv_step(h, layer, cfg: TransformerConfig, tails, row, act):
    b, c, x = _conv_project(h, layer, cfg)
    tail = lax.dynamic_index_in_dim(tails, row, keepdims=False)
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        y, fresh = gconv.gconv_step(
            tail, b[:, 0], c[:, 0], x[:, 0], layer["g_conv"], act
        )
    with tracing.scope(tracing.SCOPE_KV_WRITE):
        tails = lax.dynamic_update_index_in_dim(tails, fresh, row, 0)
    return y[:, None, None], (tails,)


_OUT = {"kda": "lo", "ssd": "s_out", "conv": "g_out"}
_SEQUENCE = {"kda": _kda_sequence, "ssd": _ssd_sequence, "conv": _conv_sequence}
_STEP = {"kda": _kda_step, "ssd": _ssd_step, "conv": _conv_step}


# ------------------------------------------------------ a layer of either kind


def layer_forward(model, x, layer, linear: bool):
    """One layer of either kind on a whole sequence [B, S, D] → (x, what
    a slot keeps of it, a tuple: ``(state, conv tail)`` or ``(conv
    tail,)`` of a linear
    layer, ``(rows [B, S, rank + rope],)`` of a latent one, ``(K rows, V
    rows [B, S, K * Dh])`` of a grouped-query one; the routing [B, S,
    top_k] or None)."""
    cfg = model.cfg
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        h = _rms_norm(x, layer["ln1"], cfg.norm_eps)
    if linear:
        attn, state, tail = attend_sequence(h, layer, cfg)
        kept = (tail,) if state is None else (state, tail)
        wo = out_projection(layer, cfg)
    elif cfg.is_mla:
        q_nope, q_rope, rows = mla.project(
            h, layer, cfg, model._seq_positions(x.shape[1])
        )
        attn = mla.attend_full(
            q_nope, q_rope, rows, layer, cfg, use_flash=model._use_flash
        )
        attn, kept, wo = gate_heads(attn, h, layer, cfg), (rows,), layer["wo"]
    else:
        # The grouped-query layer the dense paths run; a position's kv
        # heads side by side in one row, as a pool by kind holds them.
        q, k, v = model._gqa_qkv(
            h, layer, model._seq_positions(x.shape[1]), cfg.rope_theta
        )
        attn, wo = model._gqa_attend(q, k, v), layer["wo"]
        kept = tuple(a.reshape(*a.shape[:2], -1) for a in (k, v))
    x, routing = _attn_tail_routing(x, attn, {**layer, "wo": wo}, cfg)
    return x, kept, routing


def slot_layer_step(x, layer, linear: bool, row, caches, pos_b, act, cfg):
    """One decode token a slot through a linear layer, or a latent one.
    x [B, 1, D]; ``caches`` = (the linear layers' kept tensors: states
    where the kind has one, conv tails; the attention layers'
    pools: the latent pool [L, B, M, rank + rope]), ``row`` the layer's
    row in its kind's tensors. Returns (x, caches, routing [B, 1, top_k] |
    None). A grouped-query layer's token goes through the dense path's
    own step (``kvcache/slot_pool.py::_slot_layer_step``)."""
    n = kept_tensors(cfg)
    kept, pools = caches[:n], caches[n:]
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        h = _rms_norm(x, layer["ln1"], cfg.norm_eps)
    if linear:
        attn, kept = attend_step(h, layer, cfg, kept, row, act)
        wo = out_projection(layer, cfg)
    else:
        (pool,) = pools
        q_nope, q_rope, latent = mla.project(h, layer, cfg, pos_b[:, None])
        with tracing.scope(tracing.SCOPE_KV_WRITE):
            pool = pool.at[row, jnp.arange(pool.shape[1]), pos_b].set(
                latent[:, 0].astype(pool.dtype)
            )
        attn = mla.attend_absorbed(q_nope, q_rope, pool, row, pos_b, layer, cfg)
        attn, wo, pools = gate_heads(attn, h, layer, cfg), layer["wo"], (pool,)
    x, routing = _attn_tail_routing(x, attn, {**layer, "wo": wo}, cfg)
    return x, (*kept, *pools), routing


def hybrid_forward(params, model, x: jax.Array):
    """A hybrid config's layers over the embedded tokens x [B, S, D] →
    (the stream after the last layer, before the final norm; what a slot
    keeps: the states [L_lin, B, H, ., .] float32 (where the kind has
    one), the conv tails
    (``slot_shapes``) and the attention layers' rows, the latent rows
    [L_att, B, S, rank + rope] or the K and the V rows [L_att, B, S, K *
    Dh], each over its kind's layers in order; the expert layers' routing
    [L_moe, B, S, top_k] or None)."""
    cfg = model.cfg
    kinds = {
        True: tuple([] for _ in range(kept_tensors(cfg))),
        False: ([],) if cfg.is_mla else ([], []),
    }
    routing = []
    for key, pattern, _lin0, _lat0 in hybrid_groups(cfg):
        def step(x, layer, linear, _row):
            x, kept, chosen = layer_forward(model, x, layer, linear)
            return x, (kept, chosen)

        x, ys = scan_hybrid(cfg, params[key], pattern, x, step)
        # A layer of the period: its kept tensors stacked over the
        # periods; the kind's layers in order are period-major.
        for kind, into in kinds.items():
            kept = [y[0] for y, lin in zip(ys, pattern) if lin == kind]
            for t, dest in enumerate(into if kept else ()):
                stacked = jnp.stack([k[t] for k in kept], axis=1)
                dest.append(stacked.reshape(-1, *stacked.shape[2:]))
        if ys[0][1] is not None:
            chosen = jnp.stack([y[1] for y in ys], axis=1)
            routing.append(chosen.reshape(-1, *chosen.shape[2:]))

    def cat(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    *states, tails = kinds[True]  # (a state stays float32)
    kept = tuple(cat(s) for s in states) + tuple(
        cat(rows).astype(cfg.dtype) for rows in (tails, *kinds[False])
    )
    return x, kept, cat(routing) if routing else None
