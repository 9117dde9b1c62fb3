"""Plain reference of one chip's share of the LongCat-Flash decoder (the
language model of LongCat-Flash-Omni): the shortcut-connected double
layer, latent attention with compressed queries and the two scale
factors, a softmax router over real and zero-compute experts.

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, no batching, attention
UN-absorbed (the latent is up-projected to every head's key and value at
every position), and the experts by a plain loop over the HELD experts:
each multiplies every token and the result is weighted by the routing's
weight, zero where the token did not choose it. It imports nothing of the
program and takes nothing the program made: the weights are drawn again
from the seed by the family's draw (``chipbench.models.longcat_decoder``,
which imports the program inside its bridge functions only), a block or a
branch at a time, so the published widths fit the chip.

With ``h`` the stream, ``N_*`` RMSNorm, ``D`` the hidden size, ``F`` a
dense SwiGLU, for each layer:

    a0 = h  + MLA_0(N_in0(h));  m = N_post0(a0)
    s  = Experts(m)                                  the shortcut branch
    b0 = a0 + F_0(m)
    a1 = b0 + MLA_1(N_in1(b0))
    h  = a1 + F_1(N_post1(a1)) + s                   the branch rejoins

    MLA(u): cq = N_q(u W_qa) sqrt(D / q_rank);  q = cq W_qb -> [H, nope|rope]
            [c | k_r] = u W_kva;  c = N_kv(c) sqrt(D / rank)
            cached: concat(c, rope(k_r));  [k_nope | v] = c W_kvb
            scores = (q_nope k_nope + rope(q_rope) rope(k_r)) / sqrt(nope + rope)
            out = concat_heads(softmax_causal(scores) v) W_o
    Experts(m): p = softmax(m W_r) over E + Z;  sel = top_k(p + b)
            w_k = p[sel_k] routed_scaling            (no normalisation)
            s = sum_k w_k E_sel_k(m);  E_e = SwiGLU for a HELD e,
            E_e(m) = m for e >= E (a zero expert), and a pair that chose
            an expert held elsewhere adds nothing: s is this chip's part.

Departures from the source: ``rms_norm_eps`` is the file's (1e-6, the
program's constant); the selection bias is the seed's draw; the weights
are random (the file's ``assumed``); the encoders, the codec decoder and
the multi-token-prediction head are not part of the language model's
forward and are not here.

``variant`` (the serving loops call it ``lowp``) is the control of "How
``correct`` is decided": the same function with something wrong, put in
the program's place. ``True`` rounds every matmul's operands to 8-bit
floating point (e4m3, as ``reference.dense_decoder``), a ``LOWP_PARTS``
name a part of them; ``FAULTS`` names a fault of the mechanisms this
family adds: the expert branch left out, its zero experts' term left out,
the held range one expert off, the second block attending over the first
block's cached rows, a scale factor dropped. The benchmark's runs never
use any.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.models import longcat_decoder as family
from chipbench.reference.dense_decoder import _mm, rms_norm
from chipbench.reference.mla_moe_decoder import rope_pairs

_ARCH: dict = {}
LOWP_PARTS = {
    False: frozenset(), True: frozenset(
        ("proj", "read", "experts", "router", "head")
    ),
    "layers": frozenset(("proj", "read", "experts")),
    "experts": frozenset(("experts",)), "read": frozenset(("read",)),
}
FAULTS = (
    "no_branch", "no_zero_term", "held_off_by_one", "block1_reads_block0",
    "no_q_scale", "no_kv_scale",
)
HEAD_GROUP = 8  # heads attended at once: [8, S, S] float32 scores
HEAD_CHUNK = 256  # positions whose logits are formed at once


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def _parts(variant) -> frozenset:
    return LOWP_PARTS.get(variant, frozenset())


def cache_row(h, w, a: family.Arch, variant=False):
    """What a block caches of its normed input ``h`` [S, D], a function
    of each position's own row: ``concat(c, rope(k_r))`` [S, rank +
    rope]."""
    kva = _mm("sd,dc->sc", h, w["wkva"], "proj" in _parts(variant))
    c = rms_norm(kva[:, : a.rank], w["kv_norm"], a.rms_eps)
    if a.scale_kv and variant != "no_kv_scale":
        c = c * math.sqrt(a.hidden / a.rank)
    return jnp.concatenate([c, rope_pairs(kva[:, a.rank:], a.rope_theta)], -1)


def attention(x, w, a: family.Arch, variant, kv_from=None):
    """One row [S, D] through a block's attention, residual added; also
    what a cache would hold of it, ``concat(c, k_r)`` [S, rank + rope].
    ``kv_from``: another block's cached rows to attend over (a fault)."""
    s = x.shape[0]
    proj, read = "proj" in _parts(variant), "read" in _parts(variant)
    h = rms_norm(x, w["ln1"], a.rms_eps)
    cq = rms_norm(_mm("sd,dq->sq", h, w["wqa"], proj), w["q_norm"], a.rms_eps)
    if a.scale_q and variant != "no_q_scale":
        cq = cq * math.sqrt(a.hidden / a.q_rank)
    q = _mm("sq,qhe->she", cq, w["wqb"], proj)
    q_nope, q_rope = q[..., : a.nope], rope_pairs(q[..., a.nope:], a.rope_theta)
    cached = cache_row(h, w, a, variant)
    over = cached if kv_from is None else kv_from
    c, k_r = over[:, : a.rank], over[:, a.rank:]
    kv = _mm("sr,rhe->she", c, w["wkvb"], proj)
    k_nope, v = kv[..., : a.nope], kv[..., a.nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for g in range(0, a.heads, HEAD_GROUP):  # a few heads at a time
        hs = slice(g, g + HEAD_GROUP)
        sc = _mm("she,the->hst", q_nope[:, hs], k_nope[:, hs], read)
        sc = sc + _mm("she,te->hst", q_rope[:, hs], k_r, read)
        sc = sc / math.sqrt(a.nope + a.rope)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(_mm("hst,the->she", p, v[:, hs], read))
    out = _mm("she,hed->sd", jnp.concatenate(outs, axis=1), w["wo"], proj)
    return x + out, cached


def swiglu(h, gate, up, down, lowp: bool):
    g = jax.nn.silu(_mm("sd,df->sf", h, gate, lowp))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, up, lowp), down, lowp)


def route(m, w, a: family.Arch, variant):
    """(chosen outputs [S, K] over the router's E + Z, weights [S, K])."""
    p = jax.nn.softmax(
        _mm("sd,de->se", m, w["router"], "router" in _parts(variant)), axis=-1
    )
    _, idx = jax.lax.top_k(p + w["router_bias"], a.top_k)
    return idx, jnp.take_along_axis(p, idx, axis=-1) * a.scaling


def experts(m, w, a: family.Arch, variant):
    """The branch on its input ``m`` [S, D]: (this chip's part of the
    sum, the held experts' part of that, the chosen outputs [S, K]).
    ``w``'s experts are those of ``a``'s held range."""
    idx, weights = route(m, w, a, variant)
    if variant == "no_branch":
        return jnp.zeros_like(m), jnp.zeros_like(m), idx
    low = "experts" in _parts(variant)
    combine = jnp.zeros((m.shape[0], a.router_width), jnp.float32).at[
        jnp.arange(m.shape[0])[:, None], idx
    ].set(weights)
    held = jax.lax.dynamic_slice_in_dim(
        combine, a.held_first, a.held_count, axis=1
    )

    def one_expert(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * swiglu(m, gate, up, down, low), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (w["we_gate"], w["we_up"], w["we_down"], held.T),
    )
    if variant == "no_zero_term":
        return y, y, idx
    return y + combine[:, a.experts:].sum(-1, keepdims=True) * m, y, idx


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


def _held(arch: family.Arch, variant) -> family.Arch:
    """The share a fault of the held range computes: one expert up (down
    where the range ends with the experts)."""
    if variant != "held_off_by_one":
        return arch
    up = arch.held_first + arch.held_count < arch.experts
    return arch.hold(arch.held_first + (1 if up else -1), arch.held_count)


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    return family.draw(key, arch, "embed", 0, dtype)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "arch", "dtype", "variant"))
def _attend(key, x, kv_from, layer, block, arch, dtype, variant):
    """A block's attention on [B, S, D], a row at a time → (a, N_post(a),
    the rows a cache would hold [B, S, rank + rope])."""
    w = _f32(family.block_weights(key, arch, layer, block, dtype))
    rows, cached = [], []
    for row in range(x.shape[0]):
        y, latent = attention(
            x[row], w, arch, variant,
            None if kv_from is None else kv_from[row],
        )
        rows.append(y)
        cached.append(latent)
    a = jnp.stack(rows)
    return a, rms_norm(a, w["ln2"], arch.rms_eps), jnp.stack(cached)


@functools.partial(jax.jit, static_argnames=("block", "arch", "dtype", "variant"))
def _ffn(key, m, layer, block, arch, dtype, variant):
    w = _f32({
        n: family.draw(key, arch, n, 2 * layer + block, dtype)
        for n in ("w_gate", "w_up", "w_down")
    })
    low = "experts" in _parts(variant)
    return jnp.stack([
        swiglu(row, w["w_gate"], w["w_up"], w["w_down"], low) for row in m
    ])


@functools.partial(jax.jit, static_argnames=("arch", "dtype", "variant"))
def _branch(key, m, layer, arch, dtype, variant):
    w = _f32(family.branch_weights(key, arch, layer, dtype))
    out = [experts(row, w, arch, variant) for row in m]
    return tuple(jnp.stack([o[i] for o in out]) for i in range(3))


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _imprint(key, x, local, layer, arch, dtype):
    """How a part ``local`` [B, S, D] of the stream ``x`` entering layer
    ``layer`` shows in the rows its first block caches: those rows less
    what they would be without the part, [B, S, rank + rope]; exactly
    zero at a position whose part is zero."""
    w = _f32(family.block_weights(key, arch, layer, 0, dtype))

    def rows(stream):
        return jnp.stack([
            cache_row(rms_norm(row, w["ln1"], arch.rms_eps), w, arch)
            for row in stream
        ])

    return rows(x) - rows(x - local)


def layer_forward(key, x, layer, arch: family.Arch, dtype, variant=False):
    """One double layer on [B, S, D] float32 → (the stream after it, the
    branch's input ``m``, its output ``s`` and the held experts' part of
    it, the outputs chosen [B, S, K], the two blocks' cached rows [2, B,
    S, rank + rope])."""
    a0, m, lat0 = _attend(key, x, None, layer, 0, arch, dtype, variant)
    s, local, idx = _branch(
        key, m, layer, _held(arch, variant), dtype, variant
    )
    b0 = a0 + _ffn(key, m, layer, 0, arch, dtype, variant)
    a1, m1, lat1 = _attend(
        key, b0, lat0 if variant == "block1_reads_block0" else None,
        layer, 1, arch, dtype, variant,
    )
    h = a1 + _ffn(key, m1, layer, 1, arch, dtype, variant) + s
    return h, (m, s, local), idx, jnp.stack([lat0, lat1])


@functools.partial(
    jax.jit, static_argnames=("arch", "dtype", "lowp", "first", "count")
)
def _head_gaps(key, x, probe, arch, dtype, lowp, first, count):
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)
    w = family.draw(key, arch, "lm_head", 0, dtype).astype(jnp.float32)
    chunk = HEAD_CHUNK if count % HEAD_CHUNK == 0 else count

    def some(args):
        xs, ps = args  # [B, chunk, D], [B, chunk]
        logits = _mm("bsd,dv->bsv", xs, w, lowp)
        got = jnp.take_along_axis(logits, ps[..., None], axis=-1)[..., 0]
        return logits.max(-1) - got, jnp.argmax(logits, -1).astype(jnp.int32)

    b = x.shape[0]
    gap, top = jax.lax.map(some, (
        x.reshape(b, count // chunk, chunk, -1).swapaxes(0, 1),
        probe.reshape(b, count // chunk, chunk).swapaxes(0, 1),
    ))
    return (gap.swapaxes(0, 1).reshape(b, count),
            top.swapaxes(0, 1).reshape(b, count))


def forward(seed: int, arch: family.Arch, dtype, tokens, variant=False,
            cached=None, routing=None, imprints=None):
    """Hidden states after the last layer [B, T, D]. The lists given are
    filled layer by layer, on the host: ``cached`` with the blocks' rows
    ([2, B, T, rank + rope] a layer), ``routing`` with the chosen outputs
    [B, T, K], ``imprints`` (from the second layer on) with how the held
    experts' part of the layer BEFORE shows in the rows the layer's first
    block caches (``_imprint``)."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    for layer in range(arch.layers):
        if imprints is not None and layer:
            imprints.append(jax.device_get(
                _imprint(key, x, local, layer, arch, dtype)
            ))
        x, (_m, _s, local), idx, latents = layer_forward(
            key, x, layer, arch, dtype, variant
        )
        for kept, value in ((cached, latents), (routing, idx)):
            if kept is not None:
                kept.append(jax.device_get(value))
    return x


def served_logit_gaps(
    seed: int, dims: W.Dims, tokens, first: int, count: int,
    lowp=False, probe=None,
):
    """As ``reference.dense_decoder.served_logit_gaps``: teacher-forced
    forward over ``tokens`` [B, T]; ``gap[b, j]`` is how far the served
    token ``j``'s logit lies below the row's best at position ``first +
    j``, ``top[b, j]`` the reference's first choice there."""
    arch, dtype = _ARCH[dims]
    tokens = jnp.asarray(tokens, jnp.int32)
    if probe is None:
        probe = tokens[:, first + 1: first + 1 + count]
    x = forward(seed, arch, dtype, tokens, lowp)
    return _head_gaps(
        W.seed_key(seed), x, jnp.asarray(probe, jnp.int32), arch, dtype,
        "head" in _parts(lowp), first, count,
    )


def cached_rows(seed: int, dims: W.Dims, tokens, lowp=False):
    """What a cache would hold of ``tokens`` [B, T], teacher-forced:
    ``concat(c, k_r)`` of every block, [2L, B, T, rank + rope] float32 on
    the host, block ``i`` of layer ``l`` at ``2l + i``."""
    arch, dtype = _ARCH[dims]
    rows: list = []
    forward(seed, arch, dtype, jnp.asarray(tokens, jnp.int32), lowp, rows)
    return np.concatenate(rows)


def share_rows(seed: int, dims: W.Dims, tokens):
    """``cached_rows`` [2L, B, T, rank + rope] and, from the same forward,
    what tells whether a served program computed its HELD experts: how
    each layer's held experts' part shows in the NEXT layer's first
    block's row at the same position ([L - 1, B, T, rank + rope]; zero
    where the token chose no held expert), and the outputs chosen [L, B,
    T, K]."""
    arch, dtype = _ARCH[dims]
    rows: list = []
    chosen: list = []
    imprints: list = []
    forward(
        seed, arch, dtype, jnp.asarray(tokens, jnp.int32), cached=rows,
        routing=chosen, imprints=imprints,
    )
    return np.concatenate(rows), np.stack(imprints), np.stack(chosen)
