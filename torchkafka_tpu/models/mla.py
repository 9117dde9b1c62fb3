"""Latent attention (MLA, DeepSeek-V2/V3): the projections, the two
attention paths, and what is cached.

A block's weights (``models/transformer.py::_arch_shapes``): ``wq`` [D, H,
nope + rope], or with query compression (``q_lora_rank`` > 0) ``wqa`` [D,
q_rank], ``q_norm`` [q_rank] and ``wqb`` [q_rank, H, nope + rope]; ``wkva``
[D, rank + rope] (the latent and the key all heads share), ``kv_norm``
[rank], ``wkvb`` [rank, H, nope + v] (a head's k_nope beside its v), ``wo``
[H, v, D].

What is cached, one tensor a block: ``concat(norm(c), rope(k_r))``,
``rank + rope`` wide, in the compute dtype; with ``mla_scale_kv_lora`` the
normed latent times ``sqrt(D / rank)``, the scaled latent being what the
heads' keys and values are made from.

Two paths, the same mathematics reordered:

- **un-absorbed** (``attend_full``; prefill, training-shaped): the latent
  is up-projected to every head's k_nope and v, and the heads attend as
  usual, q·k over ``nope + rope`` and p·v over ``v``; through the flash
  kernel where the sequence tiles.
- **absorbed** (``attend_absorbed``; decode through the pool): ``W_uk``
  (``wkvb``'s key half) is folded into the query, ``q_lat = q_nope·W_uk``,
  so a head's query meets the cached rows themselves, MQA-shaped:
  ``scores = (q_lat·c + q_rope·k_r)/sqrt(nope + rope)``, ``o_lat = p·c``,
  and ``W_uv`` (the value half) is applied after, ``o = o_lat·W_uv``.
  Nothing is up-projected for the pool's thousands of positions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from torchkafka_tpu.models.quant import load_weight
from torchkafka_tpu.models.transformer import TransformerConfig, _rms_norm, _rope
from torchkafka_tpu.utils import tracing


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def project(h, layer, cfg: TransformerConfig, positions):
    """Normed activations h [B, S, D] at ``positions`` ([S] or [B, S]) →
    (q_nope [B, S, H, nope], q_rope [B, S, H, rope] roped, latent [B, S,
    rank + rope]: what the cache holds)."""
    if cfg.q_lora_rank:
        cq = _rms_norm(
            jnp.einsum("bsd,dq->bsq", h, load_weight(layer["wqa"], cfg.dtype)),
            layer["q_norm"],
        )
        if cfg.mla_scale_q_lora:
            cq = cq * jnp.asarray(
                math.sqrt(cfg.d_model / cfg.q_lora_rank), cq.dtype
            )
        q = jnp.einsum("bsq,qhe->bshe", cq, load_weight(layer["wqb"], cfg.dtype))
    else:
        q = jnp.einsum("bsd,dhe->bshe", h, load_weight(layer["wq"], cfg.dtype))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    kva = jnp.einsum("bsd,dc->bsc", h, load_weight(layer["wkva"], cfg.dtype))
    c, k_r = jnp.split(kva, [cfg.kv_lora_rank], axis=-1)
    c = _rms_norm(c, layer["kv_norm"])
    if cfg.mla_scale_kv_lora:
        c = c * jnp.asarray(math.sqrt(cfg.d_model / cfg.kv_lora_rank), c.dtype)
    q_rope = _rope(q_rope, positions, cfg.rope_theta, cfg.rope_interleave)
    k_r = _rope(
        k_r[:, :, None, :], positions, cfg.rope_theta, cfg.rope_interleave
    )[:, :, 0, :]
    return q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1)


def _scale(cfg: TransformerConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_head_dim)


def attend_full(q_nope, q_rope, latent, layer, cfg, *, use_flash: bool):
    """Causal self-attention over a whole sequence, un-absorbed →
    [B, S, H, v]."""
    b, s, h, _ = q_nope.shape
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        c, k_r = jnp.split(latent, [cfg.kv_lora_rank], axis=-1)
        kv = jnp.einsum("bsr,rhe->bshe", c, load_weight(layer["wkvb"], cfg.dtype))
        k_nope, v = jnp.split(kv, [cfg.qk_nope_dim], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, :, None, :], (b, s, h, k_r.shape[-1]))],
            axis=-1,
        )
    return _attend_whole(q, k, v, cfg, use_flash)


@tracing.scope(tracing.SCOPE_ATTN_FLASH)
def _attend_whole(q, k, v, cfg, use_flash: bool):
    """``attend_full``'s attention proper, heads up-projected."""
    s = q.shape[1]
    if use_flash:
        from torchkafka_tpu.ops.flash import flash_forward

        out = flash_forward(q, k, v, scale=_scale(cfg))
        if out is not None:
            return out
    scores = jnp.einsum(
        "bqhe,bkhe->bhqk", q, k, preferred_element_type=jnp.float32
    ) * _scale(cfg)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    return jnp.einsum(
        "bhqk,bkhe->bqhe", probs.astype(cfg.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)


def attend_absorbed(q_nope, q_rope, pool, l, pos_b, layer, cfg):
    """One decode query a slot against row ``l`` (a layer's, or a block's
    of the double layer) of the stacked latent pool [L, B, M, rank +
    rope], positions 0..pos_b valid → [B, 1, H, v]."""
    r = cfg.kv_lora_rank
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        w_uk, w_uv = jnp.split(
            load_weight(layer["wkvb"], cfg.dtype), [cfg.qk_nope_dim], axis=-1
        )
        q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, w_uk)
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B, 1, H, C]
    o_lat = _read_latent(q_cat, pool, l, pos_b, cfg)
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        return jnp.einsum("bshr,rhe->bshe", o_lat[..., :r], w_uv)


@tracing.scope(tracing.SCOPE_KV_READ_LATENT)
def _read_latent(q_cat, pool, l, pos_b, cfg):
    """``attend_absorbed``'s read: the absorbed queries [B, 1, H, rank +
    rope] against row ``l`` of the pool → [B, 1, H, rank + rope], the
    roped key's columns still there."""
    slab = jax.lax.dynamic_index_in_dim(pool, l, keepdims=False)
    scores = jnp.einsum(
        "bshc,bmc->bhsm", q_cat, slab, preferred_element_type=jnp.float32
    ) * _scale(cfg)
    valid = jnp.arange(slab.shape[1])[None, :] <= pos_b[:, None]  # [B, M]
    probs = jax.nn.softmax(
        jnp.where(valid[:, None, None, :], scores, -1e30), axis=-1
    )
    # Over the slab's full width, the roped key's columns dropped after: a
    # ``slab[..., :r]`` operand is a copy of the slab (0.3 GB a layer a
    # tick at the benchmark's pool), this reads it in place.
    return jnp.einsum(
        "bhsm,bmc->bshc", probs.astype(cfg.dtype), slab,
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype)
