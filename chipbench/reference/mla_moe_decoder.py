"""Plain reference of the latent-attention, routed-expert decoder
(``deepseek_v3`` as Kanana-2-30B-A3B configures it: MLA without q
compression, a leading dense layer, sigmoid-routed experts with shared
ones).

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, no batching, attention
UN-absorbed (the latent is up-projected to every head's key and value at
every position, and the heads attend as in any decoder), and the experts
by a plain loop: every expert multiplies every token and the result is
weighted by the routing's weight, zero where the token did not choose it.
It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by the family's draw
(``chipbench.models.mla_moe_decoder``, which imports the program inside
its bridge functions only), one layer at a time.

The layer, for ``x`` [S, D] of one row:

    h = norm1(x); q = h W_q -> [H, nope + rope]; (c, k_r) = h W_kva
    c = norm_kv(c); q_rope, k_r roped over interleaved pairs (2i, 2i+1)
    (k_nope, v) = c W_kvb -> [H, nope + v]
    scores = (q_nope k_nope + q_rope k_r) / sqrt(nope + rope), causal
    x += softmax(scores) v W_o
    h2 = norm2(x)
    dense layer:  x += SwiGLU(h2)
    expert layer: s = sigmoid(h2 W_r); sel = top_k(s + b)
                  w = s[sel] / sum(s[sel]) * routed_scaling
                  x += sum_k w_k E_sel_k(h2) + S(h2)

Departures from the source: none in the mathematics. ``rms_norm_eps`` is
the file's (1e-6, also the program's constant); the selection bias is the
seed's draw (the file's ``assumed``); the weights are random.

``lowp`` is the control of "How ``correct`` is decided": the same
function with matmul operands first rounded to 8-bit floating point
(e4m3, scaled by the row's largest magnitude), the precision below the
bfloat16 the configuration states. ``lowp=True`` rounds every matmul's
operands, the embedding's product with the head included (what
``loops/serve.py::control`` asks for). A name rounds a part alone and
leaves the router, the embedding and the head as they are
(``LOWP_PARTS``): ``"layers"`` the attention's and the experts' matmuls,
``"experts"`` the MLPs' alone (a program that streamed its experts in 8
bits behind a sound head), ``"read"`` the attention's products with the
cached positions alone (one that read its latent pool in 8 bits);
``loops/serve_latent.py::control`` asks for these. The benchmark's runs
never use any.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.models import mla_moe_decoder as family
from chipbench.reference.dense_decoder import _mm, rms_norm

# ``loops/serve.py`` hands a reference ``weights.Dims`` and nothing else
# of the configuration; the family's sizes and the dtype its weights are
# stored in are kept here by them (``family.program_config`` registers).
_ARCH: dict = {}
# What each ``lowp`` rounds: the projections (q, kv_a, kv_b, o), the
# attention's products with the cached positions (q k and p v), the MLPs
# (dense, routed and shared experts), the router, the head.
LOWP_PARTS = {
    False: frozenset(), True: frozenset(
        ("proj", "read", "experts", "router", "head")
    ),
    "layers": frozenset(("proj", "read", "experts")),
    "experts": frozenset(("experts",)), "read": frozenset(("read",)),
}
HEAD_GROUP = 8  # heads attended at once: [8, S, S] float32 scores
HEAD_CHUNK = 256  # positions whose logits are formed at once


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def _cos_sin(x: jax.Array, theta: float):
    """cos and sin of ``position * theta**(-2i/E)`` for x [S, ..., E],
    position = row index, shaped to broadcast against a pair's members."""
    e = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), e // 2)
    return jnp.cos(ang), jnp.sin(ang)


def rope_pairs(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over interleaved pairs (2i, 2i+1)."""
    cos, sin = _cos_sin(x, theta)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def rope_halves(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the split halves (i, i + E/2)."""
    cos, sin = _cos_sin(x, theta)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, w, a: family.Arch, lowp):
    """One row [S, D] through the layer's attention, residual added; also
    what a cache would hold of it, ``concat(c, k_r)`` [S, rank + rope]."""
    s = x.shape[0]
    proj, read = "proj" in LOWP_PARTS[lowp], "read" in LOWP_PARTS[lowp]
    rope = rope_pairs if a.rope_interleave else rope_halves
    h = rms_norm(x, w["ln1"], a.rms_eps)
    q = _mm("sd,dhe->she", h, w["wq"], proj)
    q_nope, q_rope = q[..., : a.nope], rope(q[..., a.nope:], a.rope_theta)
    kva = _mm("sd,dc->sc", h, w["wkva"], proj)
    c = rms_norm(kva[:, : a.rank], w["kv_norm"], a.rms_eps)
    k_r = rope(kva[:, a.rank:], a.rope_theta)
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
    return x + out, jnp.concatenate([c, k_r], axis=-1)


def swiglu(h, gate, up, down, lowp: bool):
    g = jax.nn.silu(_mm("sd,df->sf", h, gate, lowp))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, up, lowp), down, lowp)


def route(h, w, a: family.Arch, lowp):
    """(chosen experts [S, K], their weights [S, K])."""
    scores = jax.nn.sigmoid(
        _mm("sd,de->se", h, w["router"], "router" in LOWP_PARTS[lowp])
    )
    _, idx = jax.lax.top_k(scores + w["router_bias"], a.top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, picked / (picked.sum(-1, keepdims=True) + 1e-20) * a.scaling


def mlp(x, w, a: family.Arch, lowp):
    """One row [S, D] through the layer's MLP, residual added; also the
    experts chosen ([S, K], or None for a dense layer)."""
    h = rms_norm(x, w["ln2"], a.rms_eps)
    low = "experts" in LOWP_PARTS[lowp]
    if "router" not in w:
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], low), None
    idx, weights = route(h, w, a, lowp)
    combine = jnp.zeros((h.shape[0], a.experts), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx
    ].set(weights)

    def one_expert(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * swiglu(h, gate, up, down, low), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], combine.T),
    )
    y = y + swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], low)
    return x + y, idx


def layer_forward(x, w, a: family.Arch, lowp=False):
    """One decoder layer on [B, S, D] float32, a row at a time →
    ([B, S, D], the experts chosen [B, S, K] or None, the rows a cache
    would hold [B, S, rank + rope])."""
    rows, routing, cached = [], [], []
    for row in range(x.shape[0]):
        y, latent = attention(x[row], w, a, lowp)
        y, idx = mlp(y, w, a, lowp)
        rows.append(y)
        routing.append(idx)
        cached.append(latent)
    routing = None if routing[0] is None else jnp.stack(routing)
    return jnp.stack(rows), routing, jnp.stack(cached)


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    return family.draw(key, arch, "embed", 0, dtype)[tokens].astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("expert", "arch", "dtype", "lowp")
)
def _layer(key, x, layer, expert, arch, dtype, lowp):
    w = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(key, arch, layer, dtype, expert),
    )
    return layer_forward(x, w, arch, lowp)


@functools.partial(
    jax.jit, static_argnames=("arch", "dtype", "lowp", "first", "count")
)
def _head_gaps(key, x, probe, arch, dtype, lowp, first, count):
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)
    w = family.draw(key, arch, "lm_head", 0, dtype).astype(jnp.float32)
    # A few hundred positions at a time: 3,584 positions of four rows
    # against 128,256 rows of the head are 7 GB of float32 logits at once.
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


def forward(seed: int, arch: family.Arch, dtype, tokens, lowp=False,
            cached=None):
    """Hidden states after the last layer [B, T, D] and each expert
    layer's choices, a list of [B, T, K]. ``cached``, a list, is given
    every layer's cached rows [B, T, rank + rope] (on the host: seven
    layers of four long rows are not kept on the device)."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    routing = []
    for layer in range(arch.layers):
        x, idx, latent = _layer(
            key, x, layer, arch.is_expert_layer(layer), arch, dtype, lowp
        )
        if idx is not None:
            routing.append(idx)
        if cached is not None:
            cached.append(jax.device_get(latent))
    return x, routing


def served_logit_gaps(
    seed: int, dims: W.Dims, tokens, first: int, count: int,
    lowp=False, probe=None,
):
    """As ``reference.dense_decoder.served_logit_gaps``: teacher-forced
    forward over ``tokens`` [B, T]; ``gap[b, j]`` is how far the served
    token ``j``'s logit lies below the row's best at position ``first +
    j``, ``top[b, j]`` the reference's first choice there. The seed's key
    is an argument of every jitted function, never a constant in one."""
    arch, dtype = _ARCH[dims]
    tokens = jnp.asarray(tokens, jnp.int32)
    if probe is None:
        probe = tokens[:, first + 1: first + 1 + count]
    x, _routing = forward(seed, arch, dtype, tokens, lowp)
    return _head_gaps(
        W.seed_key(seed), x, jnp.asarray(probe, jnp.int32), arch, dtype,
        "head" in LOWP_PARTS[lowp], first, count,
    )


def cached_rows(seed: int, dims: W.Dims, tokens, lowp=False):
    """What a cache would hold of ``tokens`` [B, T], teacher-forced:
    ``concat(c, k_r)`` of every layer, [L, B, T, rank + rope] float32 on
    the host."""
    arch, dtype = _ARCH[dims]
    rows: list = []
    forward(seed, arch, dtype, jnp.asarray(tokens, jnp.int32), lowp, rows)
    return np.stack(rows)
