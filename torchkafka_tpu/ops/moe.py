"""The routed expert layer: sigmoid-scored token-choice routing and one
expert matmul over the token-choice pairs, shared by prefill and decode.

The softmax family in ``models/transformer.py`` (``_moe_mlp``: every
expert for every token; ``_moe_mlp_capacity``: Switch dispatch with drops)
stays as it is for its configs. This layer serves the DeepSeek-V3 kind
(``TransformerConfig.router_score == "sigmoid"``):

    s   = sigmoid(h · W_r)                       float32, [N, E]
    sel = top_k(s + b)                           b moves the SELECTION only
    w   = s[sel] / sum(s[sel]) * routed_scaling  the bias is not in a weight
    y   = sum_k w_k · E_sel_k(h) + S(h)          S: the shared experts

No token is dropped at any load: the pairs are sorted by expert and each
expert multiplies exactly the rows routed to it (``lax.ragged_dot``, a
grouped matmul), so the FLOPs are top-k's, not E's.

Where the rows are few against the experts (a decode tick: 64 rows x 6
choices over 128 experts touches 95% of the experts, so the weights are
streamed whole either way) the all-experts einsum is the other form of
the same sum. Which one runs is decided by the static shapes alone
(``_GROUPED_MIN_PAIRS_PER_EXPERT``), never by an option; PERF.md holds
the chip's readings of both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from torchkafka_tpu.models.quant import load_weight

# Token-choice pairs an expert must average before the sorted, grouped
# form is taken: below it the all-experts einsum streams the same weights
# and skips the sort (PERF.md, PR 27, has both readings on the v5e).
_GROUPED_MIN_PAIRS_PER_EXPERT = 8


def route(h, router, bias, *, top_k: int, scaling: float):
    """h [N, D] → (idx [N, K] int32, weights [N, K] float32).

    Scores in float32 at the matmul's highest precision: a near-tie
    between the k-th and the (k+1)-th expert should not flip on the
    matmul's rounding (it still can on ``h``'s own)."""
    logits = jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
    return idx.astype(jnp.int32), weights


def _swiglu(x, w_gate, w_up, w_down):
    gate = jax.nn.silu(jnp.einsum("nd,df->nf", x, w_gate))
    return jnp.einsum("nf,fd->nd", gate * jnp.einsum("nd,df->nf", x, w_up), w_down)


def grouped_experts(h, idx, weights, w_gate, w_up, w_down):
    """Σ_k w_k · E_idx_k(h) by one grouped matmul a projection.

    h [N, D]; idx, weights [N, K]; w_gate, w_up [E, D, F]; w_down
    [E, F, D]. The N·K (token, choice) pairs are sorted by expert
    (stable, so a token's rows keep their order inside a group), each
    expert multiplies its own run of rows, and the rows go back to
    their tokens by the inverse permutation, weighted and summed in
    float32."""
    n, k = idx.shape
    e = w_gate.shape[0]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # sorted pair -> pair
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    rows = h[order // k]  # [N·K, D]
    gate = jax.nn.silu(lax.ragged_dot(rows, w_gate, sizes))
    up = lax.ragged_dot(rows, w_up, sizes)
    out = lax.ragged_dot(gate * up, w_down, sizes)  # [N·K, D], sorted
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    out = out[inverse].reshape(n, k, -1).astype(jnp.float32)
    return jnp.einsum("nkd,nk->nd", out, weights).astype(h.dtype)


def all_experts(h, idx, weights, w_gate, w_up, w_down):
    """The same sum with every expert computed for every row and the
    unrouted ones weighted by zero."""
    e = w_gate.shape[0]
    combine = jnp.sum(
        jax.nn.one_hot(idx, e, dtype=jnp.float32) * weights[..., None], axis=1
    )  # [N, E]
    gate = jax.nn.silu(jnp.einsum("nd,edf->enf", h, w_gate))
    up = jnp.einsum("nd,edf->enf", h, w_up)
    out = jnp.einsum("enf,efd->end", gate * up, w_down)
    return jnp.einsum(
        "end,ne->nd", out.astype(jnp.float32), combine
    ).astype(h.dtype)


def routed_experts(h, idx, weights, w_gate, w_up, w_down):
    """The form the static shapes call for (module docstring)."""
    n, k = idx.shape
    if n * k >= _GROUPED_MIN_PAIRS_PER_EXPERT * w_gate.shape[0]:
        return grouped_experts(h, idx, weights, w_gate, w_up, w_down)
    return all_experts(h, idx, weights, w_gate, w_up, w_down)


def routed_moe_mlp(h, layer, cfg):
    """One expert layer's MLP on normed activations h [B, S, D]:
    (output [B, S, D], the routing idx [B, S, K])."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    idx, weights = route(
        x, layer["router"], layer["router_bias"],
        top_k=cfg.expert_top_k, scaling=cfg.routed_scaling,
    )
    out = routed_experts(
        x, idx, weights, *(
            load_weight(layer[n], cfg.dtype)
            for n in ("w_gate", "w_up", "w_down")
        ),
    )
    if cfg.n_shared_experts:
        out = out + _swiglu(x, *(
            load_weight(layer[n], cfg.dtype)
            for n in ("ws_gate", "ws_up", "ws_down")
        ))
    return out.reshape(b, s, d), idx.reshape(b, s, -1)
