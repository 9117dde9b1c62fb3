"""Linear-attention (KDA) layers beside latent-attention layers: a hybrid
model's layer of either kind, over a whole sequence and as one decode
token a slot, and the forward that keeps what serving holds of it.

``TransformerConfig.linear_pattern`` says which layers of a period are
linear (True) and which latent (False); the leading dense layers
(``first_dense_layers``) are linear. A group's tensors are stacked BY
KIND (``transformer.scan_hybrid``): the norms and the MLP's over every
layer of the group, a linear layer's over the group's linear layers, a
latent layer's (``models/mla.py``) over its latent layers.

A linear layer's tensors, with H heads of E = ``linear_head_dim``:
``lqkv`` [D, 3 * H * E] (q, k and v before the convolution, side by
side), ``lconv`` [taps, 3 * H * E] (a causal depthwise convolution over
the last ``linear_conv`` tokens, its own taps a channel, then SiLU),
``lf`` [D, H, E], ``l_alog`` [H] and ``l_dt`` [H, E] (the decay a channel:
``ops/kda.py::gate``), ``lb`` [D, H] (beta), ``lg`` [D, H] (the output
gate, ONE scalar a head), ``lnorm`` [E] (RMSNorm of a head's read-out) and
``lo`` [H, E, D]. A latent layer has ``wg`` [D, H] too: the same
head-wise gate before ``wo`` (``attn_gate``).

What a slot keeps of a linear layer is NOT indexed by position: the
recurrent state ``[H, E, E]`` in float32 and the conv tail, the last
``linear_conv - 1`` rows of ``lqkv``'s output (what the next token's
convolution reads). The admission leaves both as they stand after the
prompt window (the chunkwise form, ``ops/kda.py::kda_chunk``); a tick
updates the state in place, one pass (``tk_kda_step``).
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
from torchkafka_tpu.ops import kda
from torchkafka_tpu.utils import tracing


def step_form() -> str:
    """How a tick passes over the state: the Pallas kernel on the TPU,
    ``jax.numpy`` elsewhere."""
    return "kernel" if jax.default_backend() == "tpu" else "xla"


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
    """A linear layer's attention over a whole sequence from an empty
    state: normed h [B, S, D] → (the gated read-outs [B, S, H, E], the
    state after the last token [B, H, E, E] float32, the conv tail [B,
    taps - 1, 3 * H * E])."""
    qkv, g, beta, out_gate = _project(h, layer, cfg)
    rows = jnp.pad(qkv, ((0, 0), (cfg.linear_conv - 1, 0), (0, 0)))
    q, k, v = _conv_qkv(rows, layer, cfg)
    with tracing.scope(tracing.SCOPE_ATTN_FLASH):
        o, state = kda.kda_chunk(q, k, v, g, beta)
    return (
        _finish(o, out_gate, layer, cfg), state,
        rows[:, rows.shape[1] - (cfg.linear_conv - 1):],
    )


def attend_step(h, layer, cfg: TransformerConfig, states, tails, row, act):
    """One decode token a slot: normed h [B, 1, D] against row ``row`` of
    the stacked states [L, B, H, E, E] and conv tails [L, B, taps - 1, 3
    * H * E] → (the gated read-out [B, 1, H, E], states, tails). ``act``
    [B] bool or None: a slot that is not active keeps its state and its
    tail as they are (it decays nothing, g 0, and corrects nothing, beta
    0: the kernel writes back what it read)."""
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
    return _finish(o[:, None], out_gate, layer, cfg), states, tails


def layer_forward(model, x, layer, linear: bool):
    """One layer of either kind on a whole sequence [B, S, D] → (x, what
    a slot keeps of it, a tuple: ``(state, conv tail)`` of a linear
    layer, ``(rows [B, S, rank + rope],)`` of a latent one; the routing
    [B, S, top_k] or None)."""
    cfg = model.cfg
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        h = _rms_norm(x, layer["ln1"])
    if linear:
        attn, state, tail = attend_sequence(h, layer, cfg)
        kept, wo = (state, tail), layer["lo"]
    else:
        q_nope, q_rope, rows = mla.project(
            h, layer, cfg, model._seq_positions(x.shape[1])
        )
        attn = mla.attend_full(
            q_nope, q_rope, rows, layer, cfg, use_flash=model._use_flash
        )
        attn, kept, wo = gate_heads(attn, h, layer, cfg), (rows,), layer["wo"]
    x, routing = _attn_tail_routing(x, attn, {**layer, "wo": wo}, cfg)
    return x, kept, routing


def slot_layer_step(x, layer, linear: bool, row, caches, pos_b, act, cfg):
    """One decode token a slot through a layer of either kind. x [B, 1,
    D]; ``caches`` = (states, conv tails, the latent pool [L, B, M, rank
    + rope]), ``row`` the layer's row in its kind's tensors. Returns (x,
    caches, routing [B, 1, top_k] | None)."""
    states, tails, pool = caches
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        h = _rms_norm(x, layer["ln1"])
    if linear:
        attn, states, tails = attend_step(
            h, layer, cfg, states, tails, row, act
        )
        wo = layer["lo"]
    else:
        q_nope, q_rope, latent = mla.project(h, layer, cfg, pos_b[:, None])
        with tracing.scope(tracing.SCOPE_KV_WRITE):
            pool = pool.at[row, jnp.arange(pool.shape[1]), pos_b].set(
                latent[:, 0].astype(pool.dtype)
            )
        attn = mla.attend_absorbed(q_nope, q_rope, pool, row, pos_b, layer, cfg)
        attn, wo = gate_heads(attn, h, layer, cfg), layer["wo"]
    x, routing = _attn_tail_routing(x, attn, {**layer, "wo": wo}, cfg)
    return x, (states, tails, pool), routing


def hybrid_forward(params, model, x: jax.Array):
    """A hybrid config's layers over the embedded tokens x [B, S, D] →
    (the stream after the last layer, before the final norm; what a slot
    keeps: the states [L_lin, B, H, E, E] float32, the conv tails [L_lin,
    B, taps - 1, 3 * H * E] and the latent rows [L_lat, B, S, rank +
    rope], each over its kind's layers in order; the expert layers'
    routing [L_moe, B, S, top_k] or None)."""
    cfg = model.cfg
    states, tails, latents, routing = [], [], [], []
    for key, pattern, _lin0, _lat0 in hybrid_groups(cfg):
        def step(x, layer, linear, _row):
            x, kept, chosen = layer_forward(model, x, layer, linear)
            return x, (kept, chosen)

        x, ys = scan_hybrid(cfg, params[key], pattern, x, step)
        # A layer of the period: its kept tensors stacked over the
        # periods; the kind's layers in order are period-major.
        for kind, into in ((True, (states, tails)), (False, (latents,))):
            kept = [y[0] for y, lin in zip(ys, pattern) if lin == kind]
            for t, dest in enumerate(into if kept else ()):
                stacked = jnp.stack([k[t] for k in kept], axis=1)
                dest.append(stacked.reshape(-1, *stacked.shape[2:]))
        if ys[0][1] is not None:
            chosen = jnp.stack([y[1] for y in ys], axis=1)
            routing.append(chosen.reshape(-1, *chosen.shape[2:]))

    def cat(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    kept = (
        cat(states), cat(tails).astype(cfg.dtype),
        cat(latents).astype(cfg.dtype),
    )
    return x, kept, cat(routing) if routing else None
