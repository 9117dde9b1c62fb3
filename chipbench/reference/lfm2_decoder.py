"""Plain reference of the first pipeline stage of the LFM2-8B-A1B decoder
(``lfm2_moe``): gated short convolutions that keep no state, grouped-query
attention with a norm a head on q and k, two leading dense layers, 32
sigmoid-routed experts whose bias moves the selection alone, a tied head.

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, no batching, the convolution
as an explicit sum over three shifted copies, attention as a masked
softmax a row, the experts by a plain loop over ALL of them. It imports
nothing of the program and takes nothing the program made: the weights are
drawn again from the seed by the family's draw
(``chipbench.models.lfm2_decoder``, which imports the program inside its
bridge functions only), a layer at a time; a row (a request) at a time
through attention and the experts, so that windows of 2,048 tokens fit.

``x0 = E[ids]`` (rows not scaled). For layer ``i``, with ``h =
RMSNorm(x; operator_norm)`` (eps ``norm_eps`` everywhere):

    conv:   [B | C | X] = h W_in (the thirds in this order, no bias)
            u = B . X;  v_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t
            (depthwise, causal, zeros before the first token, NO
            activation);  y = C . v;  x <- x + y W_out
            a slot would keep u_{t-2}, u_{t-1}
    attention: q = h W_q (32 heads of 64), k = h W_k, v = h W_v (8 heads),
            no bias; q and k each RMSNorm'd over a head's 64 with a
            learned weight BEFORE the rotation; rope theta 1e6 over split
            halves; causal softmax of q.k / 8; 4 q heads a kv head
            x <- x + o W_o;  a cache would keep the normed, rotated K row
            beside the V row
    h2 = RMSNorm(x; ffn_norm)
    i < 2:  x <- x + (SiLU(h2 W_1) . h2 W_3) W_2 at 7,168
    else:   s = sigmoid(h2 W_r) (32, float32); the four largest of s + b
            (b moves the selection alone);  g = s[sel] / (sum s[sel] +
            1e-6) . routed_scaling_factor
            x <- x + sum g_e (SiLU(h2 W1_e) . h2 W3_e) W2_e at 1,792
    logits = RMSNorm(x; embedding_norm) E^T, E the embedding

Departures from the family's public modelling code
(``transformers/models/lfm2_moe/modeling_lfm2_moe.py``), each because the
benchmark serves one chip of a pipeline or has no checkpoint: layers 0 to
9 of 24 and the head straight after them (the later stages are not
here); weights from the seed, norms at one; the convolution written as
the sum above and not as ``conv1d`` with padding (the same function);
every expert computed for every token and weighted by a column that is
zero where it was not chosen (the same sum, no token dropped); nothing
for ``max_position_embeddings`` (it bounds nothing under 128,000).

``variant`` (the serving loops call it ``lowp``) is the control of how
``correct`` is decided: the same function with something wrong, put in
the program's place. ``True`` rounds every matmul's operands to 8-bit
floating point, a ``LOWP_PARTS`` name a part of them; ``FAULTS`` names a
fault of the mechanisms this family adds. The benchmark's runs never use
any.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.models import lfm2_decoder as family
from chipbench.reference.dense_decoder import _mm, rms_norm, rope

_ARCH: dict = {}
LOWP_PARTS = {
    False: frozenset(), True: frozenset(
        ("proj", "read", "experts", "router", "head")
    ),
    "layers": frozenset(("proj", "read", "experts")),
    "experts": frozenset(("experts",)), "read": frozenset(("read",)),
}
# The outer gate C left out (y = v); the taps in reverse order (w_2 on the
# oldest row); q and k rotated without the norm a head; the bias added to
# the scores the gates are made of, and not to the selection alone; the
# router's logits, scores and gates rounded to bfloat16 where float32 is
# stated.
FAULTS = (
    "no_c_gate", "taps_reversed", "no_head_norm", "bias_in_gates",
    "router_bf16",
)
CONTROLS = (True, *FAULTS)
# What the LAST layer adds to the stream, which no slot keeps, compared by
# its parts: the mixer's output and the experts' sum (the share of each
# that the program's stream lacks).
LAST_PARTS = ("mixer", "experts")
GATE_EPS = 1e-6
HEAD_CHUNK = 256  # positions whose logits are formed at once
ROWS_AT_ONCE = 16  # rows whose slot memory one pass forms


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def _parts(variant) -> frozenset:
    return LOWP_PARTS.get(variant, frozenset())


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def conv_mixer(x, w, a: family.Arch, variant, snap_at=None):
    """[B, T, D] through a gated convolution, residual added → (x, the
    mixer's part of it [B, T, D], the tail a slot would keep after the
    last token [B, taps - 1, D], oldest row first, and after ``snap_at``
    tokens, else None)."""
    t = x.shape[1]
    h = rms_norm(x, w["ln1"], a.rms_eps)
    bcx = _mm("btd,dc->btc", h, w["g_in"], "proj" in _parts(variant))
    b, c, xs = jnp.split(bcx, 3, axis=-1)
    u = b * xs
    rows = jnp.pad(u, ((0, 0), (a.taps - 1, 0), (0, 0)))
    taps = w["g_conv"][::-1] if variant == "taps_reversed" else w["g_conv"]
    v = sum(rows[:, i:i + t] * taps[i] for i in range(a.taps))
    y = v if variant == "no_c_gate" else c * v
    part = _mm(
        "bti,id->btd", y, w["g_out"].reshape(a.hidden, -1),
        "proj" in _parts(variant),
    )

    def tail_after(n):
        return rows[:, n: n + a.taps - 1]

    snap = tail_after(snap_at) if snap_at and 0 < snap_at < t else None
    return x + part, part, tail_after(t), snap


def attention(x, w, a: family.Arch, variant):
    """One row [S, D] through the attention layer, residual added → (x,
    the mixer's part [S, D], what a cache would hold of it [S, 2 * K *
    Dh]: the normed, rotated K row beside the V row)."""
    s = x.shape[0]
    proj, read = "proj" in _parts(variant), "read" in _parts(variant)
    h = rms_norm(x, w["ln1"], a.rms_eps)
    q = _mm("sd,dhe->she", h, w["wq"], proj)
    k = _mm("sd,dke->ske", h, w["wk"], proj)
    v = _mm("sd,dke->ske", h, w["wv"], proj)
    if variant != "no_head_norm":
        q = rms_norm(q, w["q_head_norm"], a.rms_eps)
        k = rms_norm(k, w["k_head_norm"], a.rms_eps)
    q, k = rope(q[None], a.rope_theta)[0], rope(k[None], a.rope_theta)[0]
    rep = a.heads // a.kv_heads
    qg = q.reshape(s, a.kv_heads, rep, a.head)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = _mm("skre,tke->krst", qg, k, read) * a.head ** -0.5
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    heads = _mm("krst,tke->skre", p, v, read).reshape(s, a.heads, a.head)
    part = _mm("she,hed->sd", heads, w["wo"], proj)
    cached = jnp.concatenate([k.reshape(s, -1), v.reshape(s, -1)], -1)
    return x + part, part, cached


def swiglu(h, gate, up, down, lowp: bool):
    g = jax.nn.silu(_mm("sd,df->sf", h, gate, lowp))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, up, lowp), down, lowp)


def select(h, w, a: family.Arch, variant=False):
    """The router on normed rows h [S, D] → (chosen experts [S, K], gates
    [S, K], the selection's MARGIN [S]: the gap between the last biased
    score taken and the first left out)."""
    low = variant == "router_bf16"
    rnd = _bf16 if low else (lambda v: v)
    logits = rnd(_mm(
        "sd,de->se", rnd(h), rnd(w["router"]), "router" in _parts(variant)
    ))
    scores = rnd(jax.nn.sigmoid(logits))
    biased = rnd(scores + w["router_bias"])
    best, idx = jax.lax.top_k(biased, a.top_k + 1)
    margin = best[:, a.top_k - 1] - best[:, a.top_k]
    idx = idx[:, : a.top_k]

    picked = jnp.take_along_axis(
        biased if variant == "bias_in_gates" else scores, idx, axis=-1
    )
    total = rnd(picked.sum(-1, keepdims=True) + GATE_EPS)
    return idx, rnd(picked / total) * a.scaling, margin


def experts_mlp(h, w, a: family.Arch, variant):
    """Normed rows h [S, D] through the expert layer → (the experts' sum
    [S, D], chosen [S, K], margin [S])."""
    low = "experts" in _parts(variant)
    idx, gates, margin = select(h, w, a, variant)
    cols = jnp.zeros((h.shape[0], a.experts), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx
    ].set(gates)

    def one_expert(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * swiglu(h, gate, up, down, low), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (w["we_gate"], w["we_up"], w["we_down"], cols.T),
    )
    return out, idx, margin


def ffn(x, w, a: family.Arch, dense: bool, variant):
    """One row [S, D] through the layer's FFN, residual added → (x, its
    parts: ``experts`` what was added, ``chosen`` [S, K], ``margin`` [S],
    ``router_in`` the normed rows the router read)."""
    h = rms_norm(x, w["ln2"], a.rms_eps)
    if dense:
        low = "experts" in _parts(variant)
        part = swiglu(h, w["w_gate"], w["w_up"], w["w_down"], low)
        return x + part, {
            "experts": part,
            "chosen": jnp.zeros((h.shape[0], a.top_k), jnp.int32),
            "margin": jnp.full((h.shape[0],), jnp.inf), "router_in": h,
        }
    part, chosen, margin = experts_mlp(h, w, a, variant)
    return x + part, {
        "experts": part, "chosen": chosen, "margin": margin, "router_in": h,
    }


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    table = family.draw(key, arch, "embed", 0, dtype).astype(jnp.float32)
    return table[tokens]


@functools.partial(
    jax.jit, static_argnames=("kind", "arch", "dtype", "variant", "snap_at")
)
def _layer(key, x, layer, kind, arch, dtype, variant, snap_at):
    """Layer ``layer`` of ``kind`` = (a convolution, a dense FFN) on [B, T,
    D] → (x, what a slot would keep of it: ``(tail, tail after snap_at |
    None)`` of a convolution, the K|V rows [B, T, 2 * K * Dh] of an
    attention layer; its parts, ``ffn``'s and ``mixer``, [B, T, ..])."""
    linear, dense = kind
    w = _f32(family.layer_weights(key, arch, layer, dtype, kind))
    if linear:
        x, mixer, tail, snap = conv_mixer(x, w, arch, variant, snap_at)
        kept = (tail, snap)
    else:
        x, mixer, kept = jax.lax.map(
            lambda row: attention(row, w, arch, variant), x
        )
    x, parts = jax.lax.map(lambda row: ffn(row, w, arch, dense, variant), x)
    return x, kept, {**parts, "mixer": mixer}


@functools.partial(
    jax.jit, static_argnames=("arch", "dtype", "lowp", "first", "count")
)
def _head_gaps(key, x, probe, arch, dtype, lowp, first, count):
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)
    w = family.draw(key, arch, "embed", 0, dtype).astype(jnp.float32)
    chunk = HEAD_CHUNK if count % HEAD_CHUNK == 0 else count

    def some(args):
        xs, ps = args  # [B, chunk, D], [B, chunk]
        logits = _mm("bsd,vd->bsv", xs, w, lowp)
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
            kept=None, snap_at=None):
    """Hidden states after the last layer [B, T, D]. ``kept``, a dict, is
    filled on the host: ``tails`` [L_lin, B, taps - 1, D] after the LAST
    token (``tails_at``: after ``snap_at`` tokens), ``rows`` [L_att, B, T,
    2 * K * Dh], ``chosen`` and ``margin`` (a list over the expert layers
    of [B, T, K] and [B, T]), and of the LAST layer, whose output no slot
    keeps, ``hidden`` (the stream after it [B, T, D]), ``last_parts``
    (``LAST_PARTS``, [2, B, T, D]) and ``router_in`` (the normed rows its
    router read [B, T, D])."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    if kept is not None:
        kept.update(tails=[], tails_at=[], rows=[], chosen=[], margin=[])
    parts = None
    for layer in range(arch.layers):
        kind = (arch.is_linear(layer), arch.is_dense(layer))
        x, held, parts = _layer(
            key, x, layer, kind, arch, dtype, variant, snap_at
        )
        if kept is None:
            continue
        if arch.is_linear(layer):
            kept["tails"].append(jax.device_get(held[0]))
            if held[1] is not None:
                kept["tails_at"].append(jax.device_get(held[1]))
        else:
            kept["rows"].append(jax.device_get(held))
        if not arch.is_dense(layer):
            kept["chosen"].append(jax.device_get(parts["chosen"]))
            kept["margin"].append(jax.device_get(parts["margin"]))
    if kept is not None:
        kept["hidden"] = [jax.device_get(x)]
        kept["router_in"] = [jax.device_get(parts["router_in"])]
        kept["last_parts"] = [jax.device_get(parts[n]) for n in LAST_PARTS]
    return x


def logits(seed: int, dims: W.Dims, tokens, variant=False):
    """Float32 logits [B, T, V] of ``tokens`` [B, T] (the tests' sizes)."""
    arch, dtype = _ARCH[dims]
    x = forward(seed, arch, dtype, tokens, variant)
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    w = family.draw(W.seed_key(seed), arch, "embed", 0, dtype)
    return _mm("bsd,vd->bsv", x, w.astype(jnp.float32), False)


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


def slot_memory(seed: int, dims: W.Dims, tokens, lowp=False,
                snap_at=None) -> dict:
    """What a slot would keep after consuming ``tokens`` [B, T],
    teacher-forced, float32 on the host: ``tails`` [L_lin, B, taps - 1, D]
    (``tails_at``: after ``snap_at`` tokens, where given), ``rows`` [L_att,
    B, T, 2 * K * Dh], the expert layers' ``chosen`` [L_moe, B, T, K] and
    ``margin`` [L_moe, B, T]; and what no slot keeps, the stream after the
    last layer ``hidden`` [B, T, D] with ``last_parts`` [2, B, T, D]
    (``LAST_PARTS``) and ``router_in`` [B, T, D]."""
    arch, dtype = _ARCH[dims]
    tokens = np.asarray(tokens, np.int32)
    some = []
    for at in range(0, len(tokens), ROWS_AT_ONCE):
        kept: dict = {}
        forward(seed, arch, dtype, tokens[at: at + ROWS_AT_ONCE], lowp, kept,
                snap_at)
        some.append({n: np.stack(v) for n, v in kept.items() if v})
    return {
        n: np.concatenate([s[n] for s in some], axis=1)[
            0 if n in ("hidden", "router_in") else slice(None)
        ] for n in some[0]
    }


def routed(seed: int, dims: W.Dims, layer: int, rows, variant=False):
    """The router of layer ``layer`` on given normed ``rows`` [N, D], a
    function of the rows alone → (chosen [N, K], gates [N, K], margin
    [N]), on the host."""
    arch, dtype = _ARCH[dims]
    key = W.seed_key(seed)
    w = {
        n: family.draw(key, arch, n, layer, dtype).astype(jnp.float32)
        for n in family.ROUTER
    }
    return jax.device_get(
        select(jnp.asarray(rows, jnp.float32), w, arch, variant)
    )
