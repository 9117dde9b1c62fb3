"""Plain reference of the window/full grouped-query, routed-expert decoder
(``mellum`` as Mellum2-12B-A2.5B configures it).

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, no ring, no batching, the
window as a mask over the whole sequence, YaRN from its formula, and the
experts by a plain loop: every expert multiplies every token and the
result is weighted by the routing's weight, zero where the token did not
choose it. It imports nothing of the program and takes nothing the program
made: the weights are drawn again from the seed by the family's draw
(``chipbench.models.mellum_decoder``, which imports the program inside its
bridge functions only), one layer at a time.

The layer, for ``x`` [S, D] of one row:

    h = norm1(x); q = h W_q -> [H, E]; k = h W_k, v = h W_v -> [K, E]
    q, k roped over split halves (i, i + E/2) by the layer's kind:
      sliding: inv_freq_i = theta^(-2i/E)
      full (YaRN): low  = floor(E ln(L0 / (beta_fast 2 pi)) / (2 ln theta))
                   high = ceil (E ln(L0 / (beta_slow 2 pi)) / (2 ln theta))
                   ramp_i = clip((i - low) / (high - low), 0, 1)
                   inv_freq_i = theta^(-2i/E) (1 - ramp_i)
                              + theta^(-2i/E) / factor * ramp_i
                   cos and sin times attention_factor
    scores = q k / sqrt(E), kept where j <= i, in a sliding layer also
             i - j < window
    x += softmax(scores) v W_o
    m = norm2(x); s = softmax(m W_r) in float32; sel = top_k(s)
    x += sum_k s[sel_k] / sum(s[sel]) E_sel_k(m)

Departures from the source: none in the mathematics; what ``config.json``
does not name is not built (the file's ``assumed``).

``lowp`` selects a CONTROL of "How ``correct`` is decided": the reference
with something wrong, put in the program's place (``CONTROLS``). ``True``
rounds every matmul's operands to 8-bit floating point (e4m3, scaled by
the row's largest magnitude), the precision below the bfloat16 the
configuration states; a name leaves the precision alone and breaks one
mechanism. The benchmark's runs never use any.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.models import mellum_decoder as family
from chipbench.reference.dense_decoder import _mm, rms_norm

# ``loops/serve.py`` hands a reference ``weights.Dims`` and nothing else
# of the configuration; the family's sizes and the dtype its weights are
# stored in are kept here by them (``family.program_config`` registers).
_ARCH: dict = {}
# What each control breaks. ``True``: every matmul in e4m3.
CONTROLS = (
    True,
    "no_window",  # the sliding layers attend to the whole past
    "no_yarn",  # the full layers roped as the sliding ones
    "no_attention_factor",  # YaRN's pairs, cos and sin unscaled
    "ring_short",  # a sliding layer sees one position fewer
    "no_renorm",  # the selected probabilities, not divided by their sum
)
HEAD_CHUNK = 256  # positions whose logits are formed at once


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def yarn_range(r: family.Rope, dim: int) -> tuple[int, int]:
    """(low, high): the pairs between which YaRN's ramp runs."""
    def pair_of(turns: float) -> float:
        return dim * math.log(r.original / (turns * 2 * math.pi)) / (
            2 * math.log(r.theta)
        )

    low = max(math.floor(pair_of(r.beta_fast)), 0)
    high = min(math.ceil(pair_of(r.beta_slow)), dim - 1)
    return low, high


def inv_freq(r: family.Rope, dim: int) -> jax.Array:
    """float32 [dim / 2]: the angle pair i turns a position."""
    plain = r.theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if r.factor == 1.0:
        return plain
    low, high = yarn_range(r, dim)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001), 0.0, 1.0,
    )
    return plain * (1 - ramp) + plain / r.factor * ramp


def rope(x: jax.Array, r: family.Rope, gain: bool = True) -> jax.Array:
    """x [S, H, E] at positions 0..S-1, over the split halves."""
    e = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq(r, e)
    scale = r.attention_factor if gain else 1.0
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, w, a: family.Arch, slides: bool, lowp):
    """One row [S, D] through the layer's attention, residual added."""
    s = x.shape[0]
    low = lowp is True
    r = a.rope_window if slides or lowp == "no_yarn" else a.rope_full
    gain = lowp != "no_attention_factor"
    h = rms_norm(x, w["ln1"], a.rms_eps)
    q = rope(_mm("sd,dhe->she", h, w["wq"], low), r, gain)
    k = rope(_mm("sd,dke->ske", h, w["wk"], low), r, gain)
    v = _mm("sd,dke->ske", h, w["wv"], low)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if slides and lowp != "no_window":
        seen &= i - j < a.window - (lowp == "ring_short")
    rep = a.heads // a.kv_heads
    outs = []
    for g in range(a.kv_heads):  # a kv head's queries at a time
        sc = _mm("she,te->hst", q[:, g * rep: (g + 1) * rep], k[:, g], low)
        p = jax.nn.softmax(
            jnp.where(seen, sc / math.sqrt(a.head_dim), -jnp.inf), axis=-1
        )
        outs.append(_mm("hst,te->she", p, v[:, g], low))
    out = _mm("she,hed->sd", jnp.concatenate(outs, axis=1), w["wo"], low)
    return x + out


def route(h, w, a: family.Arch, lowp):
    """(chosen experts [S, K], their weights [S, K])."""
    probs = jax.nn.softmax(
        _mm("sd,de->se", h, w["router"], lowp is True), axis=-1
    )
    picked, idx = jax.lax.top_k(probs, a.top_k)
    if lowp == "no_renorm":
        return idx, picked
    return idx, picked / picked.sum(-1, keepdims=True)


def experts(x, w, a: family.Arch, lowp):
    """One row [S, D] through the layer's expert MLP, residual added."""
    h = rms_norm(x, w["ln2"], a.rms_eps)
    low = lowp is True
    idx, weights = route(h, w, a, lowp)
    combine = jnp.zeros((h.shape[0], a.experts), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx
    ].set(weights)

    def one_expert(y, ew):
        gate, up, down, col = ew
        g = jax.nn.silu(_mm("sd,df->sf", h, gate, low))
        out = _mm("sf,fd->sd", g * _mm("sd,df->sf", h, up, low), down, low)
        return y + col[:, None] * out, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], combine.T),
    )
    return x + y


def layer_forward(x, w, a: family.Arch, slides: bool, lowp=False):
    """One decoder layer on [B, S, D] float32, a row at a time."""
    return jax.lax.map(
        lambda row: experts(attention(row, w, a, slides, lowp), w, a, lowp), x
    )


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    return family.draw(key, arch, "embed", 0, dtype)[tokens].astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("slides", "arch", "dtype", "lowp")
)
def _layer(key, x, layer, slides, arch, dtype, lowp):
    w = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(key, arch, layer, dtype),
    )
    return layer_forward(x, w, arch, slides, lowp)


@functools.partial(
    jax.jit, static_argnames=("arch", "dtype", "lowp", "first", "count")
)
def _head_gaps(key, x, probe, arch, dtype, lowp, first, count):
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)
    w = family.draw(key, arch, "lm_head", 0, dtype).astype(jnp.float32)
    # A few hundred positions at a time: 1,024 positions of four rows
    # against 98,304 rows of the head are 1.6 GB of float32 logits at once.
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


def forward(seed: int, arch: family.Arch, dtype, tokens, lowp=False):
    """Hidden states after the last layer, [B, T, D] float32."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    for layer in range(arch.layers):
        x = _layer(key, x, layer, arch.slides(layer), arch, dtype, lowp)
    return x


def logits(seed: int, arch: family.Arch, dtype, tokens, lowp=False):
    """Every position's logits [B, T, V] (the CPU tests' sizes)."""
    x = forward(seed, arch, dtype, tokens, lowp)
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    w = family.draw(W.seed_key(seed), arch, "lm_head", 0, dtype)
    return _mm("bsd,dv->bsv", x, w.astype(jnp.float32), lowp is True)


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
    x = forward(seed, arch, dtype, tokens, lowp)
    return _head_gaps(
        W.seed_key(seed), x, jnp.asarray(probe, jnp.int32), arch, dtype,
        lowp is True, first, count,
    )
