"""The routed expert layer: token-choice routing over sigmoid or softmax
scores, zero-compute experts, one chip's share of the experts, and one
expert matmul over the token-choice pairs, shared by prefill and decode.

The softmax family in ``models/transformer.py`` (``_moe_mlp``: every
expert for every token; ``_moe_mlp_capacity``: Switch dispatch with drops)
stays as it is for its configs. This layer serves the kinds that come
with latent attention, and beside grouped-query attention the experts of
a stated width of their own, routed by the renormalised softmax top-k with
no selection bias (``TransformerConfig.routed_moe``):

    s   = sigmoid(h · W_r)  or  softmax(h · W_r)     float32, [N, E + Z]
    sel = top_k(s + b)                       b moves the SELECTION only
    w   = s[sel] * routed_scaling            divided by sum(s[sel]) first
                                             if ``norm_topk``; the bias
                                             is not in a weight
    y   = sum_k w_k · E_sel_k(h) + S(h)      S: the shared experts, if any

**Zero-compute experts** (``zero_experts`` = Z > 0): the router has E + Z
outputs and ``E_e(h) = h`` for ``e >= E``: such a pair adds ``w · h`` and
touches no weight.

**A share of the experts** (``experts_held`` = (first, count)): the
weights here are those of experts ``[first, first + count)`` alone, one
chip's of an expert-parallel deployment. The router keeps all its outputs
and its top-k. A pair that chose a held expert goes through it, a pair
that chose a zero expert adds ``w · h`` (every chip computes those for its
own tokens), a pair that chose an absent expert adds nothing: the output
is THIS chip's part of the sum. Nothing stands in for the absent chips.

No token is dropped at any load, in any form:

- ``grouped_experts`` (every expert held): the pairs are sorted by expert
  and each expert multiplies exactly the rows routed to it
  (``lax.ragged_dot``), so the FLOPs are top-k's, not E's.
- ``all_experts`` (every expert held): where the rows are few against the
  experts (a decode tick) every expert multiplies every row and the
  unrouted ones are weighted by zero: the weights are streamed whole
  either way.
- ``compacted_experts`` (a share held, or zero experts behind the real
  ones; prefill and decode alike): of N·K pairs only ``count / (E + Z)``
  meet a held expert, so the local pairs are sorted to the front and
  multiplied in tiles of ``cap`` rows of one expert; the loop walks the
  tiles there are, a value of the routing and not a bound on it: all N
  rows to one expert are N / cap tiles, none is cut, and an expert no
  pair chose is not read.

Which one runs is decided by the configuration and the static shapes
alone (``_GROUPED_MIN_PAIRS_PER_EXPERT`` against the pairs an expert can
expect), never by an option; PERF.md holds the chip's readings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from torchkafka_tpu.models.quant import load_weight

# Token-choice pairs an expert must average before the sorted, grouped
# form is taken: below it the all-experts einsum streams the same weights
# and skips the sort. The grouped matmul walks tiles of 512 rows of ONE
# expert, so an expert's few rows cost it a whole tile's steps: read on the
# v5e at 3 pairs an expert (PR 27) and at 16 (PR 34: a tick of 128 rows,
# top-8 of 64), the all-experts form wins both; the admissions that take
# the grouped form average 144 pairs and more (PERF.md has the readings;
# nothing between 16 and 144 has been read).
_GROUPED_MIN_PAIRS_PER_EXPERT = 32


def route(h, router, bias, *, top_k: int, scaling: float,
          score: str = "sigmoid", norm_topk: bool = True):
    """h [N, D] → (idx [N, K] int32, weights [N, K] float32). ``bias``
    None: a router that states no selection bias.

    Scores in float32 at the matmul's highest precision: a near-tie
    between the k-th and the (k+1)-th expert should not flip on the
    matmul's rounding (it still can on ``h``'s own)."""
    logits = jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    biased = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
    else:
        weights = picked * scaling
    return idx.astype(jnp.int32), weights


def _swiglu(x, w_gate, w_up, w_down):
    gate = jax.nn.silu(jnp.einsum("nd,df->nf", x, w_gate))
    return jnp.einsum("nf,fd->nd", gate * jnp.einsum("nd,df->nf", x, w_up), w_down)


def grouped_experts(h, idx, weights, w_gate, w_up, w_down, base=None):
    """Σ_k w_k · E_idx_k(h) by one grouped matmul a projection.

    h [N, D]; idx, weights [N, K]; w_gate, w_up [E, D, F]; w_down
    [E, F, D]. The N·K (token, choice) pairs are sorted by expert
    (stable, so a token's rows keep their order inside a group), each
    expert multiplies its own run of rows, and the rows go back to
    their tokens by the inverse permutation, weighted and summed in
    float32. ``base``: the matrices are stacks of MORE than this layer's
    experts (every layer's, ``[L * E, ...]``) and expert ``i`` is row
    ``base + i``: the groups of the other rows are empty. The grouped
    matmul is a custom call, into which no slice fuses: a layer's slice
    of the stack would be copied out first, three times the experts'
    bytes a layer (PERF.md, PR 34)."""
    n, k = idx.shape
    e = w_gate.shape[0]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # sorted pair -> pair
    sizes = jnp.zeros((e,), jnp.int32).at[
        flat if base is None else base + flat
    ].add(1)
    rows = h[order // k]  # [N·K, D]
    gate = jax.nn.silu(lax.ragged_dot(rows, w_gate, sizes))
    up = lax.ragged_dot(rows, w_up, sizes)
    out = lax.ragged_dot(gate * up, w_down, sizes)  # [N·K, D], sorted
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    out = out[inverse].reshape(n, k, -1).astype(jnp.float32)
    return jnp.einsum("nkd,nk->nd", out, weights).astype(h.dtype)


def compacted_experts(h, idx, weights, w_gate, w_up, w_down, e: int,
                      cap: int, base=0):
    """Σ_k w_k · E_idx_k(h) over the pairs whose ``idx`` names one of the
    ``e`` experts HERE (``0 <= idx < e``; any other value is a pair this
    chip does not compute), in TILES of ``cap`` rows of one expert. Expert
    ``i``'s matrices are row ``base + i`` of ``w_gate``, ``w_up`` [.., D,
    F] and ``w_down`` [.., F, D]: the stacks may hold more than this
    layer's experts (every layer's, ``base`` then the layer's first), and
    ONE dynamic index reaches an expert and fuses into the product that
    reads it.

    The pairs are sorted with the local ones first, by expert; an expert
    with ``n`` pairs has ``ceil(n / cap)`` tiles, and the loop walks the
    tiles that exist, ``sum_e ceil(n_e / cap)`` of them: a value of
    ``idx``, so no routing overflows it, and a routing that sends every
    row to ONE expert costs that expert's tiles and not every expert's
    (padding tokens all route alike: PERF.md, PR 31). A tile gathers its
    rows ``[cap, D]`` (slots past the expert's run repeat a row and weigh
    nothing), multiplies them with its expert's three matrices and adds
    the weighted results to their tokens in float32."""
    n, k = idx.shape
    d = h.shape[-1]
    flat = idx.reshape(-1)
    key = jnp.where((flat >= 0) & (flat < e), flat, e)
    order = jnp.argsort(key, stable=True)  # local pairs first, by expert
    sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    starts = jnp.cumsum(sizes) - sizes
    tiles_to = jnp.cumsum((sizes + cap - 1) // cap)  # tiles up to expert e
    w_flat = weights.reshape(-1)

    def tile(t, out):
        ex = jnp.searchsorted(tiles_to, t, side="right").astype(jnp.int32)
        j = t - (tiles_to[ex] - (sizes[ex] + cap - 1) // cap)
        slot = j * cap + jnp.arange(cap)
        pair = order[jnp.minimum(starts[ex] + slot, n * k - 1)]
        tok = pair // k
        y = _swiglu(h[tok], *(
            lax.dynamic_index_in_dim(m, base + ex, keepdims=False)
            for m in (w_gate, w_up, w_down)
        )).astype(jnp.float32)  # [cap, D]
        y = y * jnp.where(slot < sizes[ex], w_flat[pair], 0.0)[:, None]
        return out.at[tok].add(y)

    out = lax.fori_loop(
        0, tiles_to[-1], tile, jnp.zeros((n, d), jnp.float32)
    )
    return out.astype(h.dtype)


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


def routed_experts(h, idx, weights, w_gate, w_up, w_down, at=None):
    """Every expert is here: the form the static shapes call for (module
    docstring). ``at`` = ``(base, count)``: the matrices are stacks and
    this layer's experts their rows ``[base, base + count)``."""
    n, k = idx.shape
    count = w_gate.shape[0] if at is None else at[1]
    if n * k >= _GROUPED_MIN_PAIRS_PER_EXPERT * count:
        return grouped_experts(
            h, idx, weights, w_gate, w_up, w_down, at and at[0]
        )
    if at is not None:
        # Few rows an expert, out of stacks: the compacted form reaches an
        # expert by ONE dynamic index, which fuses into its products. The
        # all-experts einsum over a slice of the stacks has the compiler
        # re-lay the WHOLE stacks once a dispatch (PERF.md, PR 34: 4 GB
        # of temporaries at 64 experts of 2304 x 896 in 8 layers).
        cap = -(-2 * n * k // count // 16) * 16  # as a held share's
        return compacted_experts(
            h, idx, weights, w_gate, w_up, w_down, e=count, cap=cap,
            base=at[0],
        )
    return all_experts(h, idx, weights, w_gate, w_up, w_down)


def routed_moe_mlp(h, layer, cfg, experts=None):
    """One expert layer's MLP on normed activations h [B, S, D]:
    (output [B, S, D], the routing idx [B, S, K] over ALL the router's
    outputs). With ``cfg.experts_held`` the output is this chip's part of
    the sum (module docstring). ``experts``: ``(w_gate, w_up, w_down,
    base)``, stacks whose rows ``[base, base + E)`` are this layer's
    experts, where the caller keeps them apart from the layer's other
    tensors (the double layer, or ``layer["experts_at"]`` of a scan over
    periods); default the layer's own ``w_gate``, ``w_up``, ``w_down``,
    from 0."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    idx, weights = route(
        x, layer["router"], layer.get("router_bias"),
        top_k=cfg.expert_top_k, scaling=cfg.routed_scaling,
        score=cfg.router_score, norm_topk=cfg.norm_topk,
    )
    experts = experts or layer.get("experts_at")
    *mats, base = experts or (
        *(layer[n] for n in ("w_gate", "w_up", "w_down")), 0
    )
    mats = [load_weight(m, cfg.dtype) for m in mats]
    first, count = cfg.held_experts
    if cfg.moe_partial:
        # Twice the pairs a held expert can expect, in whole sublane groups.
        cap = -(-2 * idx.size // cfg.router_width // 16) * 16
        out = compacted_experts(
            x, idx - first, weights, *mats, e=count, cap=cap, base=base
        )
    else:
        out = routed_experts(
            x, idx, weights, *mats, at=(base, count) if experts else None
        )
    if cfg.zero_experts:
        # The identity experts: w · h, no weights.
        w_zero = jnp.sum(
            jnp.where(idx >= cfg.n_experts, weights, 0.0), axis=-1
        )
        out = (
            out.astype(jnp.float32) + w_zero[:, None] * x.astype(jnp.float32)
        ).astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + _swiglu(x, *(
            load_weight(layer[n], cfg.dtype)
            for n in ("ws_gate", "ws_up", "ws_down")
        ))
    return out.reshape(b, s, d), idx.reshape(b, s, -1)
