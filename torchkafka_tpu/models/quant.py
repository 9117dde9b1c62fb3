"""Weight-only int8 quantization for inference and serving.

Net-new vs the reference (no model code in its tree, SURVEY.md §2), and
TPU-motivated: autoregressive decode is weight-bandwidth-bound (every step
streams the full parameter set from HBM for a few rows of activations), so
int8 weights halve the bytes vs bf16 — and quarter them vs f32 masters —
for ~2× the decode roofline. Activations stay in ``cfg.dtype``; weights are
dequantized per-use INSIDE the layer scan, so only one layer's bf16 weights
ever exist at a time and the HBM residency win is preserved.

Scheme: symmetric absmax, per-OUTPUT-channel (the scale reduces over each
weight's contraction axes), int8 in [-127, 127]:

    scale = absmax(w, contraction_axes) / 127
    q     = round(w / scale)              w ≈ q · scale

``QTensor`` is a pytree (NamedTuple), so quantized params flow through
jit/donation/device_put like any other param tree. The model reads weights
through ``load_weight``/``embed_rows``, which accept either a plain array
or a QTensor — training code paths are untouched (quantization is a
post-training transform; there is no QAT here).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    q: jax.Array  # int8, the original weight's shape
    scale: jax.Array  # f32, 1-sized on the contraction axes (broadcasts)


def quantize(w: jax.Array, contract_axes: tuple[int, ...]) -> QTensor:
    """Symmetric absmax int8 over ``contract_axes`` (the dims a matmul
    reduces over), leaving one scale per output channel."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=contract_axes, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=scale)


def load_weight(w, dtype):
    """Array or QTensor → compute-dtype array (dequant at the use site).
    The q·scale product runs in f32 (int8 promotes) and casts ONCE — casting
    scale to bf16 first would round it to 8 mantissa bits before the
    multiply, stacking avoidable error on top of the int8 error."""
    if isinstance(w, QTensor):
        return (w.q * w.scale).astype(dtype)
    return w.astype(dtype)


def embed_rows(w, tokens, dtype):
    """Embedding lookup for array or QTensor tables: gather int8 rows FIRST,
    then scale — never dequantizes the whole table."""
    if isinstance(w, QTensor):
        return (w.q[tokens] * w.scale[tokens]).astype(dtype)
    return w[tokens].astype(dtype)


def quant_kv_groups(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Group-wise symmetric absmax int8 over the LAST (head_dim) axis:
    [..., Dh] → (int8 [..., Dh], f32 scale [...]) — one scale per
    (position, head) group, the KV-cache analog of ``quantize``'s
    per-output-channel weight scheme. Shared by the dense int8 slot
    pool (slot_pool._slot_layer_step_q) and the int8 PAGED pool (the block
    pools quantize each written position through the same groups, so
    int8-paged serving is token-exact vs int8-dense serving — the
    groups, not just the scheme, are identical)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


# Contraction axes per weight name (stacked [L, ...] layout); embeddings are
# per-row (the gather output dim).
_LAYER_AXES = {
    "wq": (1,), "wk": (1,), "wv": (1,),  # [L, D, H, Dh] contract D
    "wo": (1, 2),  # [L, H, Dh, D] contract (H, Dh)
    "w_gate": (1,), "w_up": (1,),  # [L, D, F]
    "w_down": (1,),  # [L, F, D]
}
_MOE_AXES = {
    "w_gate": (2,), "w_up": (2,),  # [L, E, D, F] contract D
    "w_down": (2,),  # [L, E, F, D] contract F
}


def quantize_params(params: dict, cfg) -> dict:
    """Post-training int8 of every matmul/embedding weight; norms and the
    MoE router (tiny, routing-sensitive) stay in their original dtype."""
    if hasattr(cfg, "is_mla"):
        from torchkafka_tpu.models.transformer import _arch_refusal

        why = _arch_refusal(
            cfg, "quantize_params (int8 weights, the experts' among them)"
        )
        if why:
            raise ValueError(why)
    layer_axes = dict(_LAYER_AXES)
    if cfg.is_moe:
        layer_axes.update(_MOE_AXES)
    layers = {}
    for name, w in params["layers"].items():
        if name in ("ln1", "ln2", "router"):
            layers[name] = w
        else:
            layers[name] = quantize(w, layer_axes[name])
    return {
        "embed": quantize(params["embed"], (1,)),  # [V, D] per-row
        "layers": layers,
        "ln_f": params["ln_f"],
        "lm_head": quantize(params["lm_head"], (0,)),  # [D, V] per-column
    }


def quantize_specs(specs: dict, cfg) -> dict:
    """PartitionSpec tree matching ``quantize_params``'s output structure:
    each quantized leaf becomes QTensor(q=<original spec>, scale=<spec with
    the contraction axes unsharded>) — a size-1 scale dim cannot shard.
    Feed the result to ``shardings_for_mesh`` to serve quantized params on
    a tp/fsdp mesh."""
    from jax.sharding import PartitionSpec as P

    layer_axes = dict(_LAYER_AXES)
    if cfg.is_moe:
        layer_axes.update(_MOE_AXES)

    def scale_spec(spec: P, contract_axes: tuple[int, ...]) -> P:
        parts = list(spec)
        for ax in contract_axes:
            parts[ax] = None
        return P(*parts)

    def q_spec(spec: P, contract_axes: tuple[int, ...]) -> QTensor:
        return QTensor(q=spec, scale=scale_spec(spec, contract_axes))

    layers = {}
    for name, spec in specs["layers"].items():
        if name in ("ln1", "ln2", "router"):
            layers[name] = spec
        else:
            # Layer specs carry a leading pp axis over L (param_specs'
            # with_pp), so the contraction axes line up with the weights.
            layers[name] = q_spec(spec, layer_axes[name])
    return {
        "embed": q_spec(specs["embed"], (1,)),
        "layers": layers,
        "ln_f": specs["ln_f"],
        "lm_head": q_spec(specs["lm_head"], (0,)),
    }


def quantized_nbytes(tree) -> int:
    return sum(
        x.nbytes for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "nbytes")
    )
