"""Plain reference of one chip's share of the granite-4.0-h decoder
(``granitemoehybrid``): Mamba-2 state-space mixers, a grouped-query
attention layer WITHOUT positions, softmax-routed experts beside a shared
one after the mixer of EVERY layer, four multipliers, a tied head.

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, the recurrence TOKEN BY
TOKEN (a ``lax.scan`` over the positions, never the chunked scan the
program's admission runs), the experts by a plain loop over the HELD
experts. It imports nothing of the program and takes nothing the program
made: the weights are drawn again from the seed by the family's draw
(``chipbench.models.granite_decoder``, which imports the program inside
its bridge functions only), a layer at a time.

``x0 = embedding_multiplier * E[ids]``. With ``h = RMSNorm(x)`` (eps
``rms_norm_eps``) and ``r = residual_multiplier``:

    Mamba-2:  [z | xBC] = h W_in (8192, 8448);  dt = h W_dt (128)
          xBC = SiLU(conv(xBC)): a causal depthwise convolution over the
          last 4 tokens with a bias a channel;  xBC -> xs [128 heads x
          64], B [128], C [128] (ONE group: B and C are every head's)
          D_t = softplus(dt + dt_bias) a head;  A = -exp(A_log) a head
          S = exp(D_t A) S + D_t xs (x) B;  y = S C + D . xs
          y = RMSNorm(y . SiLU(z)) . w over all 8192 channels
          x <- x + r y W_out
    Attention: 32 query and 8 key/value heads of 128, no bias, NO
          rotation; causal softmax of attention_multiplier q.k
          x <- x + r o W_o
    Experts, after the mixer of every layer: h = RMSNorm(x); l = h W_r
          (72); the ten largest; g = softmax over those ten
          m = sum g_e E_e(h) over the HELD experts (a pair that chose an
          absent one adds nothing: this chip's part);  s = Shared(h)
          x <- x + r (m + s)
    logits = RMSNorm(x_L) E^T / logits_scaling, E the embedding

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
from chipbench.models import granite_decoder as family
from chipbench.reference.dense_decoder import _mm, rms_norm

_ARCH: dict = {}
LOWP_PARTS = {
    False: frozenset(), True: frozenset(
        ("proj", "read", "experts", "router", "head")
    ),
    "layers": frozenset(("proj", "read", "experts")),
    "experts": frozenset(("experts",)), "read": frozenset(("read",)),
}
# The state kept in bfloat16 between tokens; the decay ``exp(dt A)``
# rounded to bfloat16; the skip term ``D x`` left out; the conv tail one
# token early; the scores over ``sqrt(head_dim)`` in place of the stated
# multiplier; the chosen logits' softmax taken over all 72 and not
# renormalised; the held range one expert off (a held expert's weights
# under its neighbour's pairs).
FAULTS = (
    "state_bf16", "decay_bf16", "no_skip", "conv_tail_one_early",
    "sqrt_scale", "no_renorm", "held_one_off",
)
CONTROLS = (True, *FAULTS)
# What the LAST layer adds to the stream that is compared by its part.
LAST_PARTS = ("experts",)
HEAD_GROUP = 8  # heads attended at once: [8, S, S] float32 scores
HEAD_CHUNK = 256  # positions whose logits are formed at once
ROWS_AT_ONCE = 16  # rows whose slot memory one pass forms


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def _parts(variant) -> frozenset:
    return LOWP_PARTS.get(variant, frozenset())


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mamba_mixer(x, w, a: family.Arch, variant, snap_at=None):
    """[B, T, D] through a Mamba-2 layer's mixer, residual added, the
    recurrence token by token; also the state after the last token [B, H,
    P, N] and the conv tail, the last ``conv - 1`` rows of what the
    convolution reads; with ``snap_at`` the state and the tail after that
    many tokens too (else None)."""
    b, t, _ = x.shape
    proj = "proj" in _parts(variant)
    h = rms_norm(x, w["ln1"], a.rms_eps)
    zx = _mm("btd,dc->btc", h, w["s_in"], proj)
    z, pre = zx[..., : a.inner], zx[..., a.inner:]
    dt = jax.nn.softplus(_mm("btd,dh->bth", h, w["s_in_dt"], proj) + w["s_dt"])
    rows = jnp.pad(pre, ((0, 0), (a.conv - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(
        rows[:, i:i + t] * w["s_conv"][i] for i in range(a.conv)
    ) + w["s_conv_b"])
    xs = act[..., : a.inner].reshape(b, t, a.m_heads, a.m_head)
    bm = act[..., a.inner: a.inner + a.m_state]
    cm = act[..., a.inner + a.m_state:]
    decay = jnp.exp(dt * -jnp.exp(w["s_alog"]))  # [B, T, H]
    if variant == "decay_bf16":
        decay = _bf16(decay)
    skip = 0.0 if variant == "no_skip" else w["s_d"][:, None]

    # The control's state is CARRIED in bfloat16 from token to token, and
    # rounded by ``reduce_precision`` before the read-out: a cast there
    # and back inside one step the compiler may keep at excess precision.
    keep = jnp.bfloat16 if variant == "state_bf16" else jnp.float32

    def token(s, xs_t):
        x_t, dt_t, decay_t, b_t, c_t = xs_t  # [B, H, P], [B, H] x2, [B, N] x2
        s = s.astype(jnp.float32) * decay_t[..., None, None] + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        if variant == "state_bf16":
            s = _bf16(s)
        y = _mm("bhpn,bn->bhp", s, c_t, False) + skip * x_t
        return s.astype(keep), y

    def scan(s, lo, hi):
        return jax.lax.scan(token, s, tuple(
            jnp.moveaxis(v[:, lo:hi], 1, 0) for v in (xs, dt, decay, bm, cm)
        ))

    early = 1 if variant == "conv_tail_one_early" else 0

    def tail_after(n):
        return rows[:, n - early: n - early + a.conv - 1]

    state = jnp.zeros((b, a.m_heads, a.m_head, a.m_state), keep)
    snap, ys = None, []
    if snap_at is not None and 0 < snap_at < t:
        state, y0 = scan(state, 0, snap_at)
        snap, ys = (state.astype(jnp.float32), tail_after(snap_at)), [y0]
    state, y1 = scan(state, snap_at if ys else 0, t)
    y = jnp.moveaxis(jnp.concatenate(ys + [y1]), 0, 1).reshape(b, t, a.inner)
    y = rms_norm(y * jax.nn.silu(z), w["s_norm"], a.rms_eps)
    out = _mm("bti,id->btd", y, w["s_out"].reshape(a.inner, -1), proj)
    return (
        x + a.residual_mult * out, state.astype(jnp.float32), tail_after(t),
        snap,
    )


def kv_rows(h, w, a: family.Arch, variant=False):
    """What the attention layer caches of its normed input ``h`` [S, D], a
    function of each position's own row (no rotation): its K row beside
    its V row, [S, 2 * K * Dh]."""
    proj = "proj" in _parts(variant)
    k = _mm("sd,dke->ske", h, w["wk"], proj)
    v = _mm("sd,dke->ske", h, w["wv"], proj)
    return jnp.concatenate(
        [k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1)], -1
    )


def attention(x, w, a: family.Arch, variant):
    """One row [S, D] through the attention layer, residual added; also
    what a cache would hold of it [S, 2 * K * Dh]."""
    s = x.shape[0]
    proj, read = "proj" in _parts(variant), "read" in _parts(variant)
    h = rms_norm(x, w["ln1"], a.rms_eps)
    q = _mm("sd,dhe->she", h, w["wq"], proj)
    cached = kv_rows(h, w, a, variant)
    k, v = (
        half.reshape(s, a.kv_heads, a.head)
        for half in jnp.split(cached, 2, axis=-1)
    )
    rep = a.heads // a.kv_heads
    scale = a.head ** -0.5 if variant == "sqrt_scale" else a.attn_mult
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for g in range(0, a.heads, HEAD_GROUP):  # a few heads at a time
        hs = slice(g, g + HEAD_GROUP)
        kv = slice(g // rep, -(-(g + HEAD_GROUP) // rep))
        kg, vg = (jnp.repeat(m[:, kv], rep, axis=1) for m in (k, v))
        sc = scale * _mm("she,the->hst", q[:, hs], kg, read)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(_mm("hst,the->she", p, vg, read))
    heads = jnp.concatenate(outs, axis=1)
    out = _mm("she,hed->sd", heads, w["wo"], proj)
    return x + a.residual_mult * out, cached


def swiglu(h, gate, up, down, lowp: bool):
    g = jax.nn.silu(_mm("sd,df->sf", h, gate, lowp))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", h, up, lowp), down, lowp)


def select(h, w, a: family.Arch, variant):
    """(chosen experts [S, K] of the router's outputs, weights [S, K], the
    selection's MARGIN [S]: the gap between the last logit taken and the
    first left out; a token whose margin is under the program's rounding
    may be served with another expert, in a sound run too)."""
    logits = _mm("sd,de->se", h, w["router"], "router" in _parts(variant))
    best, idx = jax.lax.top_k(logits, a.top_k + 1)
    margin = best[:, a.top_k - 1] - best[:, a.top_k]
    if variant == "no_renorm":
        weights = jnp.take_along_axis(
            jax.nn.softmax(logits, -1), idx[:, : a.top_k], axis=-1
        )
    else:
        weights = jax.nn.softmax(best[:, : a.top_k], axis=-1)
    return idx[:, : a.top_k], weights, margin


def route(h, w, a: family.Arch, variant=False):
    """(chosen experts [S, K] of the router's outputs, weights [S, K])."""
    return select(h, w, a, variant)[:2]


def mlp(x, w, a: family.Arch, variant):
    """One row [S, D] through the layer's experts, residual added → (x,
    the held experts' part of what was added [S, D], the experts chosen
    [S, K], the selection's margin [S])."""
    h = rms_norm(x, w["ln2"], a.rms_eps)
    low = "experts" in _parts(variant)
    idx, weights, margin = select(h, w, a, variant)
    combine = jnp.zeros((h.shape[0], a.experts), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx
    ].set(weights)
    first = a.held_first + (1 if variant == "held_one_off" else 0)
    # (one off the end of the router's outputs: the last held column is
    # then another expert's, as everywhere else in the range)
    held = jnp.roll(combine, -first, axis=1)[:, : a.held_count]

    def one_expert(y, ew):
        gate, up, down, col = ew
        return y + col[:, None] * swiglu(h, gate, up, down, low), None

    local, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["we_gate"], w["we_up"], w["we_down"], held.T),
    )
    local = a.residual_mult * local
    shared = swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], low)
    return x + local + a.residual_mult * shared, local, idx, margin


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    table = family.embed_rows(key, arch, dtype).astype(jnp.float32)
    return arch.embed_mult * table[tokens]


@functools.partial(
    jax.jit, static_argnames=("linear", "arch", "dtype", "variant", "snap_at")
)
def _layer(key, x, layer, linear, arch, dtype, variant, snap_at):
    """Layer ``layer`` (``linear``: a Mamba-2 mixer, else attention) on
    [B, T, D], a row at a time → (x, what a slot would keep of it:
    ``(state, conv tail, (state, tail) after snap_at | None)`` or the K|V
    rows [B, T, 2 * K * Dh]; and of its experts ``{"experts": the held
    experts' part [B, T, D], "chosen": [B, T, K], "margin": [B, T]}``)."""
    w = _f32(family.layer_weights(key, arch, layer, dtype, linear))
    if linear:
        x, state, tail, snap = mamba_mixer(x, w, arch, variant, snap_at)
        kept = (state, tail, snap)
    else:
        x, kept = jax.lax.map(lambda row: attention(row, w, arch, variant), x)
    x, local, chosen, margin = jax.lax.map(
        lambda row: mlp(row, w, arch, variant), x
    )
    return x, kept, {"experts": local, "chosen": chosen, "margin": margin}


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _imprint(key, x, local, layer, arch, dtype):
    """How a part ``local`` [B, T, D] of the stream ``x`` entering the
    attention layer ``layer`` shows in the rows it caches: those rows
    less what they would be without the part; exactly zero at a position
    whose part is zero."""
    w = _f32(family.layer_weights(key, arch, layer, dtype, False))

    def rows(stream):
        return jax.lax.map(
            lambda row: kv_rows(rms_norm(row, w["ln1"], arch.rms_eps), w, arch),
            stream,
        )

    return rows(x) - rows(x - local)


@functools.partial(
    jax.jit, static_argnames=("arch", "dtype", "lowp", "first", "count")
)
def _head_gaps(key, x, probe, arch, dtype, lowp, first, count):
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)
    w = family.embed_rows(key, arch, dtype).astype(jnp.float32)
    chunk = HEAD_CHUNK if count % HEAD_CHUNK == 0 else count

    def some(args):
        xs, ps = args  # [B, chunk, D], [B, chunk]
        logits = _mm("bsd,vd->bsv", xs, w, lowp) / arch.logits_scaling
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
    filled on the host: ``states`` [L_lin, B, H, P, N] and ``tails``
    [L_lin, B, conv - 1, C] after the LAST token (``states_at``,
    ``tails_at``: after ``snap_at`` tokens), ``rows`` [L_att, B, T, 2 * K
    * Dh], ``chosen`` (a list of [B, T, K], a layer each), ``imprint``
    (for each attention layer: how the layer before's held experts' part
    shows in the attention layer's rows, [B, T, 2 * K * Dh]) with
    ``margin`` (that layer's selection margins [B, T]), and of the LAST
    layer, whose output no slot keeps, ``hidden`` (the stream after it
    [B, T, D]) and ``last_parts`` (``experts``: its held experts' part of
    that stream, [1, B, T, D])."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    if kept is not None:
        kept.update(states=[], tails=[], states_at=[], tails_at=[], rows=[],
                    chosen=[], imprint=[], margin=[])
    parts = None
    for layer in range(arch.layers):
        linear = arch.is_linear(layer)
        if kept is not None and not linear and parts is not None:
            kept["imprint"].append(jax.device_get(_imprint(
                key, x, parts["experts"], layer, arch, dtype
            )))
            kept["margin"].append(jax.device_get(parts["margin"]))
        x, held, parts = _layer(
            key, x, layer, linear, arch, dtype, variant, snap_at
        )
        if kept is None:
            continue
        if linear:
            kept["states"].append(jax.device_get(held[0]))
            kept["tails"].append(jax.device_get(held[1]))
            if held[2] is not None:
                kept["states_at"].append(jax.device_get(held[2][0]))
                kept["tails_at"].append(jax.device_get(held[2][1]))
        else:
            kept["rows"].append(jax.device_get(held))
        kept["chosen"].append(jax.device_get(parts["chosen"]))
    if kept is not None:
        kept["hidden"] = [jax.device_get(x)]
        kept["last_parts"] = [jax.device_get(parts[n]) for n in LAST_PARTS]
    return x


def logits(seed: int, dims: W.Dims, tokens, variant=False):
    """Float32 logits [B, T, V] of ``tokens`` [B, T] (the tests' sizes)."""
    arch, dtype = _ARCH[dims]
    x = forward(seed, arch, dtype, tokens, variant)
    x = rms_norm(x, jnp.ones((arch.hidden,), jnp.float32), arch.rms_eps)
    w = family.embed_rows(W.seed_key(seed), arch, dtype).astype(jnp.float32)
    return _mm("bsd,vd->bsv", x, w, False) / arch.logits_scaling


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
    teacher-forced, float32 on the host: ``states`` [L_lin, B, H, P, N],
    ``tails`` [L_lin, B, conv - 1, C] (``states_at``, ``tails_at``: after
    ``snap_at`` tokens, where given), ``rows`` [L_att, B, T, 2 * K * Dh];
    for the comparison of the held experts' part ``imprint`` [L_att, B, T,
    2 * K * Dh], ``margin`` [L_att, B, T] and ``chosen`` [L, B, T, K]; and
    what no slot keeps, the stream after the last layer ``hidden`` [B, T,
    D] with ``last_parts`` [1, B, T, D] (``LAST_PARTS``)."""
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
            0 if n == "hidden" else slice(None)
        ] for n in some[0]
    }
