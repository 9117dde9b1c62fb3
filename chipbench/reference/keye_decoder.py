"""Plain reference of the grouped-query decoder with a learned sparse
attention indexer and routed experts (``KeyeVL2``'s language model as
Keye-VL-2.0-30B-A3B configures it, on text ids).

Straightforward ``jax.numpy`` in float32 with every matmul at
``precision="highest"``: no kernel, no cache, no batching. The index
scores are the full ``[T, T]`` matrix of one row of tokens, the selection
is ``lax.top_k`` over each query's causal row, the attention a dense
softmax under the selected mask, the experts a plain loop over the experts
HELD HERE: every held expert multiplies every token and the result is
weighted by the routing's weight, zero where the token did not choose it
(a pair that chose an expert held elsewhere adds nothing, here as in the
program). It imports nothing of the program and takes nothing the program
made: the weights are drawn again from the seed by the family's draw
(``chipbench.models.keye_decoder``, which imports the program inside its
bridge functions only), one layer at a time.

The layer, for ``x`` [T, D] of one row (all layers alike):

    h = norm1(x)
    q = h W_q -> [H, E]; k = h W_k, v = h W_v -> [K, E]; no bias
    q, k roped over split halves (i, i + E/2), inv_freq_i = theta^(-2i/E),
      the position the token's index (on text ids the three streams of
      mrope_section are equal and the rotation is plain rope)
    qI = h W_Iq -> [Hi, Di]; kI = h W_Ik -> [Di]; w = h W_Iw -> [Hi]
    qI, kI roped likewise over their Di columns
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        for s <= t
    S_t = the min(topk, t + 1) positions of largest I[t, .], ties to the
          lower position (-0.0 counts as 0.0)
    o[t, j] = sum_{s in S_t} softmax_{s in S_t}(q[t, j] . k[s, j // (H/K)]
              / sqrt(E)) v[s, j // (H/K)]
    x += o W_o
    g = norm2(x); p = softmax(g W_r) in float32 over ALL the router's
      outputs; sel = top_k(p); the selected renormalised to sum 1
    x += sum_{e in sel, e held here} p_e / sum(p[sel]) W_down_e(
           silu(g W_gate_e) * g W_up_e)

Departures from the source: positive constants on the index score (DSA's
1/sqrt(Hi), 1/sqrt(Di)) change no set and are left out; the chunk sizes of
``sa_config`` are read as the blocking of the published code, with no
effect on ``S_t``; what ``config.json`` does not name is not built (the
configuration file's ``assumed``: no q/k norm, no LayerNorm on kI, no
bias anywhere, the indexer's input the layer's normed h); the vision
tower and the three-axis positions of its tokens are not built.

``lowp`` selects a CONTROL of "How ``correct`` is decided": the reference
with something wrong, put in the program's place (``CONTROLS`` on the
chip, ``TEST_CONTROLS`` in the CPU tests). ``True`` rounds every matmul's
operands to 8-bit floating point (e4m3), the precision below the
bfloat16 the configuration states; a name leaves the precision alone and
breaks one mechanism. The benchmark's runs never use any.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.models import keye_decoder as family
from chipbench.reference.dense_decoder import _mm, rms_norm

# ``loops/serve.py`` hands a reference ``weights.Dims`` and nothing else
# of the configuration; the family's sizes and the dtype its weights are
# stored in are kept here by them (``family.program_config`` registers).
_ARCH: dict = {}
# What each control breaks. ``True``: every matmul in e4m3.
CONTROLS = (
    True,
    "attend_all_valid",  # every earlier position attended: no selection
    "topk_half",  # the best topk / 2 positions
    "held_one_off",  # the held range of experts moved by one
)
TEST_CONTROLS = (
    "attend_all_valid",
    "topk_short",  # the best topk - 1 positions
    "w_unsigned",  # the heads' weights without their sign
    "index_key_before",  # position s scored by the index key of s - 1
)
# The directions ``slot_memory`` gives layer 1's rows: what each broken
# selection in layer 0 would do to them.
SHIFTS = ("attend_all_valid", "topk_half")
EDGES = ("first", "last")  # the held experts whose part alone is read
LAST_PARTS = ("attention", "experts")
HEAD_CHUNK = 256  # positions whose logits are formed at once
HEADS_AT_ONCE = 4  # heads whose [T, T] scores exist at once, at most


def register(dims: W.Dims, arch: family.Arch, deployment: dict) -> None:
    _ARCH[dims] = (arch, family.dtype_of(deployment["param_dtype"]))


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x [T, H, E] at positions 0..T-1, over the split halves."""
    e = x.shape[-1]
    inv = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def index_parts(h, w, a: family.Arch, lowp):
    """(qI [T, Hi, Di], kI [T, Di], w [T, Hi]) of normed h [T, D]."""
    low = lowp is True
    qi = rope(_mm("td,dhe->the", h, w["wiq"], low), a.rope_theta)
    ki = rope(_mm("td,de->te", h, w["wik"], low)[:, None], a.rope_theta)[:, 0]
    return qi, ki, _mm("td,dh->th", h, w["wiw"], low)


def selected(qi, ki, wt, a: family.Arch, lowp):
    """[T, T] bool: query t attends to position s."""
    t = qi.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if lowp == "attend_all_valid":
        return causal
    if lowp == "w_unsigned":
        wt = jnp.abs(wt)
    if lowp == "index_key_before":
        ki = jnp.roll(ki, 1, axis=0)
    low = lowp is True
    scores = jnp.zeros((t, t), jnp.float32)
    for j in range(a.index_heads):  # a head at a time: [T, T] each
        scores += wt[:, j, None] * jax.nn.relu(
            _mm("td,sd->ts", qi[:, j], ki, low)
        )
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 counts as 0.0
    k = {"topk_half": a.topk // 2, "topk_short": a.topk - 1}.get(lowp, a.topk)
    k = min(k, t)
    scores = jnp.where(causal, scores, -jnp.inf)
    # The k best of the row: everything above the k-th value, and of the
    # positions AT it the lowest, as many as are left (``lax.top_k``'s own
    # order of equals; written so because a scatter of its indices into a
    # [T, T] mask is 25 million writes a layer at the cell's lengths).
    level = jax.lax.top_k(scores, k)[0][:, -1:]
    above, at = scores > level, scores == level
    left = k - above.sum(-1, keepdims=True)
    return (above | (at & (jnp.cumsum(at, axis=-1) <= left))) & causal


def attention(x, w, a: family.Arch, lowp):
    """One row [T, D] -> (the attention's part o W_o [T, D], the K row
    beside the V row [T, 2 K E], the index key [T, Di], the selected
    mask [T, T])."""
    low = lowp is True
    h = rms_norm(x, w["ln1"], a.rms_eps)
    q = rope(_mm("td,dhe->the", h, w["wq"], low), a.rope_theta)
    k, v = keys_values(h, w, a, low)
    qi, ki, wt = index_parts(h, w, a, lowp)
    seen = selected(qi, ki, wt, a, lowp)
    rep = a.heads // a.kv_heads
    outs = []
    some = math.gcd(HEADS_AT_ONCE, rep)  # (never across two kv heads)
    for j in range(0, a.heads, some):  # [some, T, T] at a time
        g = j // rep
        sc = _mm("the,se->hts", q[:, j: j + some], k[:, g], low)
        p = jax.nn.softmax(
            jnp.where(seen, sc / math.sqrt(a.head_dim), -jnp.inf), axis=-1
        )
        outs.append(_mm("hts,se->the", p, v[:, g], low))
    part = _mm("the,hed->td", jnp.concatenate(outs, axis=1), w["wo"], low)
    return part, beside(k, v), ki, seen


def keys_values(h, w, a: family.Arch, low: bool):
    """(the roped keys, the values) [T, K, E] of normed h [T, D]."""
    k = rope(_mm("td,dke->tke", h, w["wk"], low), a.rope_theta)
    return k, _mm("td,dke->tke", h, w["wv"], low)


def beside(k, v):
    """A position's K row beside its V row [T, 2 K E]."""
    t = k.shape[0]
    return jnp.concatenate([k.reshape(t, -1), v.reshape(t, -1)], axis=-1)


def route(g, w, a: family.Arch, lowp):
    """(chosen outputs of the router [T, K], their weights [T, K])."""
    probs = jax.nn.softmax(
        _mm("td,de->te", g, w["router"], lowp is True), axis=-1
    )
    picked, idx = jax.lax.top_k(probs, a.top_k)
    return idx, picked / picked.sum(-1, keepdims=True)


def experts(x, w, a: family.Arch, lowp):
    """One row [T, D] -> (the held experts' part [T, D], which tokens
    have a local pair [T], the parts of the FIRST and of the LAST held
    expert alone [2, T, D]: what a held range moved by one loses)."""
    g = rms_norm(x, w["ln2"], a.rms_eps)
    low = lowp is True
    idx, weights = route(g, w, a, lowp)
    first = a.first + (lowp == "held_one_off")
    combine = jnp.zeros((g.shape[0], a.router), jnp.float32).at[
        jnp.arange(g.shape[0])[:, None], idx
    ].set(weights)
    held = jax.lax.dynamic_slice_in_dim(
        jnp.pad(combine, ((0, 0), (0, 1))), first, a.experts, axis=1
    )  # (a range moved past the router's last output holds nothing there)

    def one_expert(y, ew):
        gate, up, down, col = ew
        u = jax.nn.silu(_mm("td,df->tf", g, gate, low))
        out = _mm("tf,fd->td", u * _mm("td,df->tf", g, up, low), down, low)
        return y + col[:, None] * out, col[:, None] * out

    y, each = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], held.T),
    )
    return y, (held > 0).any(-1), jnp.stack([each[0], each[-1]])


def layer_forward(x, w, a: family.Arch, lowp=False):
    """One decoder layer on a row [T, D] float32 -> (the stream after it,
    the K|V rows, the index keys, the selected mask, the attention's part,
    the experts' part, the tokens with a local pair, the first and the
    last held expert's parts)."""
    att, rows, ki, seen = attention(x, w, a, lowp)
    x = x + att
    exp, local, edges = experts(x, w, a, lowp)
    return x + exp, rows, ki, seen, att, exp, local, edges


@functools.partial(jax.jit, static_argnames=("arch", "dtype"))
def _embed(key, tokens, arch, dtype):
    return family.draw(key, arch, "embed", 0, dtype)[tokens].astype(jnp.float32)


def _weights(key, arch, layer, dtype):
    return jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(key, arch, layer, dtype),
    )


@functools.partial(jax.jit, static_argnames=("arch", "dtype", "lowp"))
def _layer(key, x, layer, arch, dtype, lowp):
    """Layer ``layer`` over [B, T, D], a row at a time -> the stream."""
    w = _weights(key, arch, layer, dtype)
    return jax.lax.map(lambda row: layer_forward(row, w, arch, lowp)[0], x)


@functools.partial(jax.jit, static_argnames=("arch", "dtype", "lowp"))
def _layer_kept(key, x, layer, arch, dtype, lowp):
    """``_layer`` with what a slot keeps of it and its parts: (stream,
    K|V rows [B, T, 2 K E], index keys [B, T, Di], attention's part,
    experts' part, tokens with a local pair [B, T], the first and the last
    held expert's parts [B, 2, T, D])."""
    w = _weights(key, arch, layer, dtype)

    def row(x):
        out, rows, ki, _seen, att, exp, local, edges = layer_forward(
            x, w, arch, lowp
        )
        return out, rows, ki, att, exp, local, edges

    return jax.lax.map(row, x)


@functools.partial(jax.jit, static_argnames=("arch", "dtype", "lowp"))
def _rows_of(key, x, layer, arch, dtype, lowp):
    """The K|V rows layer ``layer`` writes for the stream x [B, T, D]."""
    w = _weights(key, arch, layer, dtype)
    return jax.lax.map(lambda r: attention_rows(r, w, arch, lowp), x)


def attention_rows(x, w, a: family.Arch, lowp):
    """``attention``'s rows alone (no scores, no softmax)."""
    h = rms_norm(x, w["ln1"], a.rms_eps)
    return beside(*keys_values(h, w, a, lowp is True))


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


def forward(seed: int, arch: family.Arch, dtype, tokens, lowp=False):
    """Hidden states after the last layer, [B, T, D] float32."""
    key = W.seed_key(seed)
    x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    for layer in range(arch.layers):
        x = _layer(key, x, layer, arch, dtype, lowp)
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


def slot_memory(seed: int, dims: W.Dims, tokens, lowp=False) -> dict:
    """What the slots of a server that consumed ``tokens`` [S, T] hold,
    and what the comparisons of ``loops/serve_sparse.py`` need beside it,
    float32 on the host:

    - ``rows`` [L, S, T, 2 K E] (a position's K row beside its V row) and
      ``index`` [L, S, T, Di], every layer's;
    - ``imprint`` [L, S, T, 2 K E]: how the held experts' part of the
      layer BEFORE shows in a layer's rows (the rows of the stream less
      the rows of the stream without that part; layer 0 has none), zero
      where the token has no local pair;
    - ``shifts``: for each of ``SHIFTS``, layer 1's rows had layer 0's
      selection been broken so (everything else sound);
    - ``edges``: by ``EDGES``, how the part of layer 0's FIRST and of its
      LAST held expert alone shows in layer 1's rows [S, T, 2 K E], zero
      where the token has no pair on that expert (a held range moved by
      one loses one of them whole; among sixteen held experts that moves
      no median over the tokens with a local pair);
    - ``hidden`` [S, T, D], the stream after the last layer, and
      ``last_parts``, what the last layer's attention and its held
      experts added to it (by ``LAST_PARTS``)."""
    arch, dtype = _ARCH[dims]
    key = W.seed_key(seed)
    embedded = x = _embed(key, jnp.asarray(tokens, jnp.int32), arch, dtype)
    rows, index, imprint, shifts, edges = [], [], [], {}, {}
    exp = local = edge_parts = None
    for layer in range(arch.layers):
        if layer == 1 and not lowp:
            # The stream layer 1 would read under each broken selection.
            for name in SHIFTS:
                alt = _layer(key, embedded, 0, arch, dtype, name)
                shifts[name] = np.asarray(
                    _rows_of(key, alt, 1, arch, dtype, False)
                )
        before = x
        x, r, ki, att, new_exp, new_local, new_edges = _layer_kept(
            key, before, layer, arch, dtype, lowp
        )
        r = np.asarray(r)
        if layer == 1:
            for name, part in zip(EDGES, jnp.moveaxis(edge_parts, 1, 0)):
                less = np.asarray(
                    _rows_of(key, before - part, 1, arch, dtype, lowp)
                )
                has = np.asarray(jnp.abs(part).sum(-1) > 0)[..., None]
                edges[name] = np.where(has, r - less, 0.0)
        edge_parts = new_edges
        if exp is None:
            imprint.append(np.zeros_like(r))
        else:
            without = np.asarray(
                _rows_of(key, before - exp, layer, arch, dtype, lowp)
            )
            imprint.append(
                np.where(np.asarray(local)[..., None], r - without, 0.0)
            )
        rows.append(r)
        index.append(np.asarray(ki))
        exp, local = new_exp, new_local
    return {
        "rows": np.stack(rows), "index": np.stack(index),
        "imprint": np.stack(imprint), "shifts": shifts, "edges": edges,
        "hidden": np.asarray(x),
        "last_parts": (
            np.asarray(att),
            np.where(np.asarray(local)[..., None], np.asarray(exp), 0.0),
        ),
    }
