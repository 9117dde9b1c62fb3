"""Plain reference of one chip's share of the Ling-3.0-flash decoder
(``bailing_hybrid``): linear-attention layers of the delta rule with a
decay a channel (KDA), a latent-attention layer closing each period,
sigmoid-routed experts chosen by group, a shared expert.

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, the recurrence TOKEN BY
TOKEN (a ``lax.scan`` over the positions, never the chunkwise form the
program's admission runs), latent attention un-absorbed, the experts by a
plain loop over the HELD experts. It imports nothing of the program and
takes nothing the program made: the weights are drawn again from the seed
by the family's draw (``chipbench.models.ling_decoder``, which imports
the program inside its bridge functions only), a layer at a time.

With ``x`` a layer's RMS-normed input (eps 1e-6), H heads of E = 128:

    KDA:  [q~ | k~ | v~] = x W_qkv;  each channel: a causal depthwise
          convolution over the last 4 tokens (its own 4 taps), then SiLU
          q = q'/|q'|, k = k'/|k'| a head (the sum of squares + 1e-6, as
          the program), v = v'
          beta = sigmoid(x W_b) a head;  g = -5 sigmoid(e^A_log_h (x W_f +
          dt_bias)) a CHANNEL;  alpha = exp(g)
          S' = Diag(alpha) S;  u = v - S'^T k;  S = S' + beta k u^T
          o = S^T q / sqrt(E);  out = [sigmoid(x W_g)_h RMSNorm_h(o)] W_o
    MLA:  as ``reference.mla_moe_decoder`` (no query compression,
          interleaved rope), its heads gated by sigmoid(x W_g)_h before W_o
    Experts: s = sigmoid(x W_r);  b = s + bias;  the experts fall into
          ``n_group`` groups of consecutive ones, a group scores the sum
          of its two largest b, the ``topk_group`` best stay;  sel = the
          top-k of b among them;  w = s[sel] / sum(s[sel]) * scaling
          y = sum_k w_k E_sel_k(x) over the HELD experts (a pair that
          chose an absent one adds nothing: this chip's part) + Shared(x)

``variant`` (the serving loops call it ``lowp``) is the control of how
``correct`` is decided: the same function with something wrong, put in
the program's place. ``True`` rounds every matmul's operands to 8-bit
floating point, a ``LOWP_PARTS`` name a part of them; ``FAULTS`` names a
fault of the mechanisms this family adds. The benchmark's runs never use
any.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.models import ling_decoder as family
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
# The state kept in bfloat16 between tokens; ONE decay a head (the mean of
# its channels') in place of one a channel; the gate without its bound
# (``-e^A_log softplus(..)``, the unbounded form); the top-k taken over
# every expert, no group left out; the conv tail one token early; the
# latent layer's output gate left out; the held range one expert off (a
# held expert's weights under its neighbour's pairs).
FAULTS = (
    "state_bf16", "decay_a_head", "no_safe_gate", "no_group_selection",
    "conv_tail_one_early", "no_output_gate", "held_one_off",
)
CONTROLS = (True, *FAULTS)
# What the LAST layer adds to the stream that is compared by its part.
LAST_PARTS = ("experts", "gate")
HEAD_GROUP = 8  # heads attended at once: [8, S, S] float32 scores
HEAD_CHUNK = 256  # positions whose logits are formed at once
ROWS_AT_ONCE = 16  # rows whose slot memory one pass forms
NORM_EPS = 1e-6


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def _parts(variant) -> frozenset:
    return LOWP_PARTS.get(variant, frozenset())


def log_decay(aa, w, a: family.Arch, variant):
    """g [.., H, E] of ``aa = x W_f``."""
    z = jnp.exp(w["l_alog"])[:, None] * (aa + w["l_dt"])
    if variant == "no_safe_gate":
        return -jax.nn.softplus(z)
    g = a.lower * jax.nn.sigmoid(z)
    if variant == "decay_a_head":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    return g


def linear_attention(x, w, a: family.Arch, variant):
    """[B, T, D] through a KDA layer's attention, residual added, the
    recurrence token by token; also the state after the last token [B, H,
    E, E] and the conv tail, the last ``conv - 1`` rows of ``x W_qkv``."""
    b, t, _ = x.shape
    proj = "proj" in _parts(variant)
    h = rms_norm(x, w["ln1"], a.rms_eps)
    pre = _mm("btd,dc->btc", h, w["lqkv"], proj)
    rows = jnp.pad(pre, ((0, 0), (a.conv - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(
        rows[:, i:i + t] * w["lconv"][i] for i in range(a.conv)
    ))
    q, k, v = jnp.split(act.reshape(b, t, 3 * a.heads, a.head), 3, axis=2)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + NORM_EPS)

    q, k = unit(q), unit(k)
    beta = jax.nn.sigmoid(_mm("btd,dh->bth", h, w["lb"], proj))
    gate = jax.nn.sigmoid(_mm("btd,dh->bth", h, w["lg"], proj))
    g = log_decay(_mm("btd,dhe->bthe", h, w["lf"], proj), w, a, variant)

    # The control's state is CARRIED in bfloat16 from token to token, and
    # rounded by ``reduce_precision`` before the read-out: a cast there
    # and back inside one step the compiler may keep at excess precision,
    # and on the TPU it did (PERF.md, PR 41).
    keep = jnp.bfloat16 if variant == "state_bf16" else jnp.float32

    def token(s, xs):
        q, k, v, g, beta = xs  # [B, H, E], beta [B, H]
        s = s.astype(jnp.float32) * jnp.exp(g)[..., None]
        u = v - _mm("bhkv,bhk->bhv", s, k, False)
        s = s + (beta[..., None] * k)[..., None] * u[..., None, :]
        if variant == "state_bf16":
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        o = _mm("bhkv,bhk->bhv", s, q, False) / math.sqrt(a.head)
        return s.astype(keep), o

    state, o = jax.lax.scan(
        token, jnp.zeros((b, a.heads, a.head, a.head), keep),
        tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v, g, beta)),
    )
    state = state.astype(jnp.float32)
    o = rms_norm(jnp.moveaxis(o, 0, 1), w["lnorm"], NORM_EPS)
    out = _mm("bthe,hed->btd", o * gate[..., None], w["lo"], proj)
    early = 1 if variant == "conv_tail_one_early" else 0
    tail = rows[:, t - early: t - early + a.conv - 1]
    return x + out, state, tail


def cache_row(h, w, a: family.Arch, variant=False):
    """What the latent layer caches of its normed input ``h`` [S, D], a
    function of each position's own row: ``concat(norm(c), rope(k_r))``."""
    kva = _mm("sd,dc->sc", h, w["wkva"], "proj" in _parts(variant))
    c = rms_norm(kva[:, : a.rank], w["kv_norm"], a.rms_eps)
    return jnp.concatenate([c, rope_pairs(kva[:, a.rank:], a.rope_theta)], -1)


def latent_attention(x, w, a: family.Arch, variant):
    """One row [S, D] through the latent layer's attention, residual
    added; also what a cache would hold of it [S, rank + rope], and what
    the output gate changes of what the layer adds [S, D] (the gate's
    PART: the gated heads' sum less the plain heads')."""
    s = x.shape[0]
    proj, read = "proj" in _parts(variant), "read" in _parts(variant)
    h = rms_norm(x, w["ln1"], a.rms_eps)
    q = _mm("sd,dhe->she", h, w["wq"], proj)
    q_nope, q_rope = q[..., : a.nope], rope_pairs(q[..., a.nope:], a.rope_theta)
    cached = cache_row(h, w, a, variant)
    c, k_r = cached[:, : a.rank], cached[:, a.rank:]
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
    heads = jnp.concatenate(outs, axis=1)
    gate = jax.nn.sigmoid(_mm("sd,dh->sh", h, w["wg"], proj))
    plain = _mm("she,hed->sd", heads, w["wo"], proj)
    gated = _mm("she,hed->sd", heads * gate[..., None], w["wo"], proj)
    out = plain if variant == "no_output_gate" else gated
    return x + out, cached, gated - plain


def swiglu(h, gate, up, down, lowp: bool):
    g = jax.nn.silu(_mm("sd,df->sf", h, gate, lowp))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, up, lowp), down, lowp)


def _last_gap(values, k: int):
    """How far the k-th largest of ``values`` [S, N] lies above the next."""
    best = jax.lax.top_k(values, k + 1)[0]
    return best[:, k - 1] - best[:, k]


def select(h, w, a: family.Arch, variant):
    """(chosen experts [S, K] of the router's outputs, weights [S, K], the
    selection's MARGIN [S]: the gap between the last expert taken and the
    first left out or, if smaller, between the last group kept and the
    first left out; a token whose margin is under the program's rounding
    may be served with another expert, in a sound run too)."""
    scores = jax.nn.sigmoid(
        _mm("sd,de->se", h, w["router"], "router" in _parts(variant))
    )
    biased = scores + w["router_bias"]
    margin = jnp.full(biased.shape[:1], jnp.inf)
    if a.groups > 1 and variant != "no_group_selection":
        by_group = biased.reshape(-1, a.groups, a.experts // a.groups)
        score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)  # [S, G]
        best = jax.lax.top_k(score, a.top_groups)[1]
        if a.top_groups < a.groups:
            margin = _last_gap(score, a.top_groups)
        stays = jnp.zeros(score.shape, bool).at[
            jnp.arange(score.shape[0])[:, None], best
        ].set(True)
        biased = jnp.where(
            jnp.repeat(stays, a.experts // a.groups, axis=1), biased, -jnp.inf
        )
    _, idx = jax.lax.top_k(biased, a.top_k)
    margin = jnp.minimum(margin, _last_gap(biased, a.top_k))
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * a.scaling
    return idx, weights, margin


def route(h, w, a: family.Arch, variant):
    """(chosen experts [S, K] of the router's outputs, weights [S, K])."""
    return select(h, w, a, variant)[:2]


def mlp(x, w, a: family.Arch, variant):
    """One row [S, D] through the layer's MLP, residual added → (x, the
    held experts' part of what was added [S, D], the experts chosen [S, K]
    or None for a dense layer, the selection's margin [S] or None)."""
    h = rms_norm(x, w["ln2"], a.rms_eps)
    low = "experts" in _parts(variant)
    if "router" not in w:
        y = swiglu(h, w["w_gate"], w["w_up"], w["w_down"], low)
        return x + y, jnp.zeros_like(x), None, None
    idx, weights, margin = select(h, w, a, variant)
    combine = jnp.zeros((h.shape[0], a.experts), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx
    ].set(weights)
    held = jax.lax.dynamic_slice_in_dim(
        combine, a.held_first + (1 if variant == "held_one_off" else 0),
        a.held_count, axis=1,
    )

    def one_expert(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * swiglu(h, gate, up, down, low), None

    local, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["we_gate"], w["we_up"], w["we_down"], held.T),
    )
    shared = swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], low)
    return x + local + shared, local, idx, margin


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    return family.draw(key, arch, "embed", 0, dtype)[tokens].astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("kind", "arch", "dtype", "variant")
)
def _layer(key, x, layer, kind, arch, dtype, variant):
    """Layer ``layer`` (of ``kind``: linear attention or latent, an expert
    layer or a dense one) on [B, T, D], a row at a time → (x, what a slot
    would keep of it: ``(state, conv tail)`` or the latent rows [B, T,
    rank + rope]; and of its parts ``{"experts": the held experts' part
    [B, T, D], "chosen": [B, T, K] | None, "margin": [B, T] | None,
    "gate": the output gate's part [B, T, D] | None}``)."""
    w = _f32(family.layer_weights(key, arch, layer, dtype, kind))
    gate = None
    if kind[0]:
        x, state, tail = linear_attention(x, w, arch, variant)
        kept = (state, tail)
    else:
        x, kept, gate = jax.lax.map(
            lambda row: latent_attention(row, w, arch, variant), x
        )
    x, local, chosen, margin = jax.lax.map(
        lambda row: mlp(row, w, arch, variant), x
    )
    return x, kept, {
        "experts": local, "chosen": chosen, "margin": margin, "gate": gate,
    }


@functools.partial(jax.jit, static_argnames=("kind", "arch", "dtype"))
def _imprint(key, x, local, layer, kind, arch, dtype):
    """How a part ``local`` [B, T, D] of the stream ``x`` entering the
    latent layer ``layer`` shows in the rows it caches: those rows less
    what they would be without the part; exactly zero at a position whose
    part is zero."""
    w = _f32(family.layer_weights(key, arch, layer, dtype, kind))

    def rows(stream):
        return jax.lax.map(
            lambda row: cache_row(rms_norm(row, w["ln1"], arch.rms_eps), w, arch),
            stream,
        )

    return rows(x) - rows(x - local)


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
            kept=None):
    """Hidden states after the last layer [B, T, D]. ``kept``, a dict, is
    filled on the host: ``states`` [L_lin, B, H, E, E] and ``tails``
    [L_lin, B, conv - 1, 3 * H * E] after the LAST token, ``rows`` [L_lat,
    B, T, rank + rope], ``chosen`` (a list of [B, T, K], an expert layer
    each), ``imprint`` (for each latent layer that follows an expert
    layer: how that layer's held experts' part shows in the latent
    layer's rows, [B, T, rank + rope]) with ``margin`` (that expert
    layer's selection margins [B, T]), and of the LAST layer, whose
    output no slot keeps, ``hidden`` (the stream after it [B, T, D]) and
    ``last_parts`` (``experts``, ``gate``: its held experts' part and its
    output gate's part of that stream, [2, B, T, D])."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    if kept is not None:
        kept.update(states=[], tails=[], rows=[], chosen=[], imprint=[],
                    margin=[])
    parts = None
    for layer in range(arch.layers):
        routed = parts is not None and parts["chosen"] is not None
        if kept is not None and not arch.is_linear(layer) and routed:
            kept["imprint"].append(jax.device_get(_imprint(
                key, x, parts["experts"], layer, arch.kind(layer), arch, dtype
            )))
            kept["margin"].append(jax.device_get(parts["margin"]))
        x, held, parts = _layer(
            key, x, layer, arch.kind(layer), arch, dtype, variant
        )
        if kept is None:
            continue
        if arch.is_linear(layer):
            kept["states"].append(jax.device_get(held[0]))
            kept["tails"].append(jax.device_get(held[1]))
        else:
            kept["rows"].append(jax.device_get(held))
        if parts["chosen"] is not None:
            kept["chosen"].append(jax.device_get(parts["chosen"]))
    if kept is not None:
        kept["hidden"] = [jax.device_get(x)]
        kept["last_parts"] = [
            jax.device_get(jnp.zeros_like(x) if parts[n] is None else parts[n])
            for n in LAST_PARTS
        ]
    return x


def logits(seed: int, dims: W.Dims, tokens, variant=False):
    """Float32 logits [B, T, V] of ``tokens`` [B, T] (the tests' sizes)."""
    arch, dtype = _ARCH[dims]
    x = forward(seed, arch, dtype, tokens, variant)
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    w = family.draw(W.seed_key(seed), arch, "lm_head", 0, dtype)
    return _mm("bsd,dv->bsv", x, w.astype(jnp.float32), False)


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


def slot_memory(seed: int, dims: W.Dims, tokens, lowp=False) -> dict:
    """What a slot would keep after consuming ``tokens`` [B, T],
    teacher-forced, float32 on the host: ``states`` [L_lin, B, H, E, E],
    ``tails`` [L_lin, B, conv - 1, 3 * H * E], ``rows`` [L_lat, B, T, rank
    + rope]; for the comparison of the held experts' part ``imprint``
    [L_lat, B, T, rank + rope], ``margin`` [L_lat, B, T] and ``chosen``
    [L_moe, B, T, K]; and what no slot keeps, the stream after the last
    layer ``hidden`` [B, T, D] with ``last_parts`` [2, B, T, D]
    (``LAST_PARTS``)."""
    arch, dtype = _ARCH[dims]
    tokens = np.asarray(tokens, np.int32)
    some = []
    for at in range(0, len(tokens), ROWS_AT_ONCE):
        kept: dict = {}
        forward(seed, arch, dtype, tokens[at: at + ROWS_AT_ONCE], lowp, kept)
        some.append({n: np.stack(v) for n, v in kept.items()})
    return {
        n: np.concatenate([s[n] for s in some], axis=1)[
            0 if n == "hidden" else slice(None)
        ] for n in some[0]
    }
