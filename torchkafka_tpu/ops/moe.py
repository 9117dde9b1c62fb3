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

- ``grouped_experts`` (every expert held: an admission, a forward, and a
  decode tick whose rows average ``_GROUPED_MIN_PAIRS_PER_EXPERT`` pairs
  an expert, Mellum2's 128 slots, 16; or a SHARE of them, where a held
  expert can expect ``_GROUPED_MIN_PAIRS_OUT_OF_STACKS`` local pairs:
  Ling3's tick of 384 slots, 6): the pairs are sorted by expert
  and each expert multiplies exactly the rows routed to it, in two Pallas
  kernels of this file (``tk_gmm_gate_up``: ``silu(x · w_gate) * (x ·
  w_up)`` formed in float32 and written once; ``tk_gmm_down``), so the
  FLOPs are top-k's, not E's. The kernels walk the sorted rows in blocks
  of pieces of 128 rows (``_gmm_rows``); a block that straddles experts
  is visited once by each, which multiplies the pieces its run touches;
  an expert's matrices stay in VMEM over its consecutive blocks, the next
  expert's are fetched while this one multiplies, and the stacks ``[L *
  E, ...]`` are taken whole (``_gmm``). Of a share the local pairs sort
  first, by expert, and the absent ones behind them belong to no run: the
  walk stops where the local pairs end, the kernels touch nothing past
  it, and a select drops those rows from the sum. Off the TPU the Pallas
  interpreter runs them.
- ``all_experts`` (every expert held, the layer's own ``[E, ...]``
  tensors): where the rows are fewer an expert than that (Kanana's decode
  tick, 3) every expert multiplies every row and the unrouted ones are
  weighted by zero: the weights are streamed whole either way.
- ``compacted_experts`` (the few rows an expert of a layer whose experts
  are rows of stacks, where the einsum would copy the stacks; a share
  where the grouped form is not taken: fewer local pairs a held expert
  than the floor, experts too large for the kernels' VMEM, LongCat's, or
  an admission's trip, most of whose sorted copy would be absent pairs'
  rows): of N·K pairs only ``count / (E + Z)`` meet a held expert, so
  the local pairs are sorted to the front and multiplied in tiles of
  ``cap`` rows of one expert; the loop walks the tiles there are, a value
  of the routing and not a bound on it: all N rows to one expert are N /
  cap tiles, none is cut, and an expert no pair chose is not read. Its
  body is serial: a tile's weights are not fetched while the one before
  multiplies, which experts of 75 MB hide (LongCat's tick reads 88% of
  the HBM roofline) and experts of 11.8 MB do not (Ling3's read 42%).

Which one runs is decided by the configuration and the static shapes
alone (``_form``: the floors against the pairs a held expert can expect,
whether the experts are rows of stacks, whether the kernels fit, and how
much of the sorted copy is absent pairs' rows), never by an option;
``expert_form`` names the choice for a configuration and a row count, and
PERF.md holds the chip's readings. The four held-share sites the
benchmark's cells have (TPU v5e, PR 42, ``chipbench/tick_forms.py`` with
the loop as built against the floors at 0; ms a tick, s an admission of
every slot):

    site                      pairs   local/expert  expert   form
    Ling3 tick, 384 slots      3,072  6             11.8 MB  grouped   40.44 / 35.03
      at 256 and 128 slots     2,048  4 and 2                          30.77 / 25.61, 21.20 / 15.28
    Ling3 admission trip      24,576  48            11.8 MB  compacted 7.281 / 7.302 (384 rows; 4.899 / 4.904, 2.500 / 2.511)
    LongCat tick, 128 slots    1,536  2             75 MB    compacted (the kernels would ask 123 of 128 MiB)
    LongCat admission trip    36,864  48            75 MB    compacted

An admission keeps the loop: its tiles hold 96 rows of one expert, the
products and not the weights' stream bound it, and seven eighths of the
24,576 rows the grouped form sorts and copies (126 MB a layer, a sixth of
the held experts' stream, four passes) belong to absent pairs: the chip
reads the two forms within 0.3% of each other, and the copies would add
0.3 GiB to the admit program's footprint.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchkafka_tpu.models.quant import load_weight
from torchkafka_tpu.ops.flash import _default_interpret, tpu_compiler_params
from torchkafka_tpu.utils import tracing

# Token-choice pairs an expert must average before the sorted, grouped
# form is taken, against each fallback: two crossovers that were read on
# the v5e, not a knob (PERF.md §6, PR 39 and PR 42; ms a decode tick, the
# fallback against the grouped form).
#
# Against the all-experts EINSUM over the layer's own tensors, which
# streams the same weights and skips the sort, the gather of the sorted
# rows and the inverse permutation: Kanana's tick at 3 pairs an expert
# 18.09 / 40.10, the einsum keeps it, and not by the pairs (the kernels
# are handed a copy of the layer's experts out of the layer scan). That
# kind has been read nowhere between 3 and an admission's 144 (Kanana's),
# so its floor stands at the highest point read at which a serving cell
# sits, Mellum2's 16; what it would take to go lower is in PERF.md §7.
_GROUPED_MIN_PAIRS_PER_EXPERT = 16
# Against the LOOP of one-expert tiles (experts that are rows of stacks,
# every one held or a share): Mellum2 at 16, 12, 8 and 4 pairs an expert
# (128, 96, 64, 32 slots) 24.94 / 17.53, 23.28 / 15.60, 20.53 / 13.38,
# 18.33 / 11.44; Ling3's share of 64 of 512 at 6, 4 and 2 LOCAL pairs a
# held expert (384, 256, 128 slots) 40.44 / 35.03, 30.77 / 25.61, 21.20 /
# 15.28: the grouped form wins at every point against the loop, which
# pays a tile's serial fetch whatever it is fed. The floor stands at 4,
# the lowest point both kinds were read at; LongCat's share at 2 (experts
# of 75 MB, whose fetch hides the loop's gaps: 88% of the roofline) has
# not been read grouped and does not fit the kernels as built. The loop
# gives way wherever the einsum does: the lower of the two floors counts
# out of stacks, and ``chipbench/tick_forms.py`` sets the one above to 0
# to read the grouped form at every row count.
_GROUPED_MIN_PAIRS_OUT_OF_STACKS = 4
# A share's sorted copy holds all N·K rows though ``count / width`` of
# them are local: the rows gathered and copied for NOTHING, the absent
# pairs', as a share of the held experts' weight rows (``count · 3 · F``;
# a row is D numbers on both sides) up to which the grouped form is taken.
# Ling3's tick 2,688 rows against 147,456, a fifty-fifth (15.7 MB a layer
# beside a stream of 755): grouped, 40.44 -> 35.03 ms a tick; its
# admission's trip 21,504, a seventh: the chip reads the two forms within
# 0.3% (7.281 / 7.302 s for 384 rows) and the loop stays (module
# docstring). The bound lies between the two readings.
_GROUPED_MAX_ABSENT_ROWS = 1 / 16
# What the kernels may ask of the v5e's 128 MiB of VMEM (``_gmm_vmem``:
# an expert's gate and up matrices twice, so that the next expert's are
# fetched while this one multiplies). Mellum2's experts ask 31 MiB, Ling3's
# 31; LongCat's (6144 x 2048) 123, which the compiler takes for a described
# v5e and which no chip run has read: three quarters of VMEM keeps the
# rule on this side of what was measured.
_GMM_VMEM_BYTES = 96 << 20


@tracing.scope(tracing.SCOPE_MOE_ROUTE)
def route(h, router, bias, *, top_k: int, scaling: float,
          score: str = "sigmoid", norm_topk: bool = True,
          n_group: int = 1, topk_group: int = 1):
    """h [N, D] → (idx [N, K] int32, weights [N, K] float32). ``bias``
    None: a router that states no selection bias. ``n_group`` > 1: the
    selection is limited by group (the outputs fall into ``n_group``
    groups of consecutive experts, a group scores the sum of its two best
    biased scores, the ``topk_group`` best groups stay, the top-k is taken
    among their experts).

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
    if n_group > 1:
        by_group = biased.reshape(biased.shape[0], n_group, -1)
        best = lax.top_k(lax.top_k(by_group, 2)[0].sum(-1), topk_group)[1]
        stays = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
        biased = jnp.where(stays[..., None], by_group, -jnp.inf).reshape(
            biased.shape
        )
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


# The grouped matmul's rows (PERF.md §6 has the v5e's sweep at both
# admissions' shapes). A PIECE is what one product multiplies: 128 rows,
# the MXU's own edge; Mosaic's product of a longer run of rows is slower a
# row at every shape read (512 rows in one product take 1.4 times four of
# 128), and what a skewed routing wastes is the unfilled part of a piece.
# A BLOCK is what one grid step fetches and writes, 4 pieces: fewer steps
# and longer copies, and a piece the visiting expert's run does not reach
# is skipped, so a larger block multiplies no more.
_GMM_PIECE_ROWS = 128
_GMM_BLOCK_PIECES = 4


def _gmm_rows(pairs: int) -> tuple[int, int]:
    """(rows a block, rows a piece) for ``pairs`` sorted rows: the
    constants above, the block no longer than the rows in whole pieces."""
    pieces = min(_GMM_BLOCK_PIECES, -(-pairs // _GMM_PIECE_ROWS))
    return pieces * _GMM_PIECE_ROWS, _GMM_PIECE_ROWS


def _gmm_tiles(sizes, tiles_m: int, tm: int):
    """The grouped matmul's walk over the sorted rows, in tiles (blocks)
    of ``tm`` rows aligned to the rows (tile ``i`` is rows ``[i * tm, (i +
    1) * tm)``): an expert with a run ``[start, end)`` visits every tile
    its run touches, so a tile that straddles experts is visited once by
    each and an expert with no pair visits none. Returns (offsets [E + 1],
    the expert and the tile of visit ``t`` [tiles_m + E - 1] (the most
    visits any routing makes; past ``visits`` the entries mean nothing),
    visits: a value of the routing)."""
    e = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    each = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 0)
    upto = jnp.cumsum(each)
    t = jnp.arange(tiles_m + e - 1, dtype=jnp.int32)
    expert = jnp.minimum(jnp.searchsorted(upto, t, side="right"), e - 1)
    tile = first[expert] + t - (upto[expert] - each[expert])
    return (
        jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32),
        expert.astype(jnp.int32),
        jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
        upto[-1].astype(jnp.int32),
    )


def _gmm_kernel(base_ref, offsets_ref, expert_ref, tile_ref, x_ref, *refs,
                tm: int, ts: int):
    """One visit: the tile's rows times the visiting expert's matrix (two
    matrices: ``silu(x · w_gate) * (x · w_up)``, formed in float32), kept
    for the rows of the expert's run; the tile's other rows keep what
    their own experts' visits wrote. The tile (a block of ``tm`` rows) is
    multiplied in pieces of ``ts`` rows, and a piece the run does not
    reach is skipped."""
    *w_refs, o_ref = refs
    # The ambient matmul precision is float32 operands' alone: Mosaic
    # refuses a float32 contraction of bf16 operands.
    dot = functools.partial(
        jnp.dot, preferred_element_type=jnp.float32,
        precision=None if x_ref.dtype == jnp.float32 else lax.Precision.DEFAULT,
    )
    t = pl.program_id(0)
    ex = expert_ref[t]
    row0 = tile_ref[t] * tm
    start, end = offsets_ref[ex], offsets_ref[ex + 1]

    def piece(i, _):
        at = pl.ds(pl.multiple_of(i * ts, ts), ts)
        first = row0 + i * ts

        @pl.when((start < first + ts) & (end > first))
        def _multiply():
            x = x_ref[at, :]
            y = dot(x, w_refs[0][...])
            if len(w_refs) == 2:
                y = jax.nn.silu(y) * dot(x, w_refs[1][...])
            row = first + lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
            own = (row >= start) & (row < end)
            o_ref[at, :] = jnp.where(own, y.astype(o_ref.dtype), o_ref[at, :])

    # A loop, not the pieces one after the other: the kernel's code is one
    # piece's, and a process loads it with every program that holds it.
    lax.fori_loop(0, tm // ts, piece, None)


def _gmm_vmem(mats: int, kdim: int, n: int, tm: int, ts: int, item: int) -> int:
    """The VMEM ``_gmm`` asks for, in bytes: two buffers a block (an
    expert's ``mats`` whole ``[kdim, n]`` matrices, the rows in and out),
    a piece's float32 products, room for the rest."""
    vmem = 2 * item * (mats * kdim * n + tm * (kdim + n))
    return vmem + 4 * ts * n * (mats + 1) + (8 << 20)


@tracing.scope(tracing.SCOPE_MOE_EXPERTS)
def _gmm(rows, mats, base, walk, tm: int, ts: int, name: str):
    """rows [M, K] sorted by expert, M a multiple of the block ``tm``, a
    multiple of the piece ``ts``; mats: one ``[.., K, N]`` stack (rows ·
    W) or two (the gated pair); expert ``i`` is row ``base + i`` of a
    stack. → [M, N] in ``rows``' dtype, accumulated in float32. The grid
    is the walk's visits; a matrix block is an expert's whole ``[K, N]``
    and its index does not change over the expert's consecutive visits,
    so it is fetched once an expert."""
    m, kdim = rows.shape
    n = mats[0].shape[-1]
    offsets, expert, tile, visits = walk
    interpret = _default_interpret()
    vmem = _gmm_vmem(len(mats), kdim, n, tm, ts, rows.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, ts=ts),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits,),
            in_specs=[
                pl.BlockSpec((tm, kdim), lambda t, b, o, e, i: (i[t], 0)),
                *(
                    pl.BlockSpec(
                        (None, kdim, n),
                        lambda t, b, o, e, i: (b[0] + e[t], 0, 0),
                    )
                    for _ in mats
                ),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda t, b, o, e, i: (i[t], 0)),
        ),
        interpret=interpret,
        name=name,
        **({} if interpret else tpu_compiler_params(
            ("arbitrary",), vmem_limit_bytes=vmem
        )),
    )(jnp.asarray(base, jnp.int32).reshape(1), offsets, expert, tile,
      rows, *mats)


def grouped_experts(h, idx, weights, w_gate, w_up, w_down, at=None,
                    share=False):
    """Σ_k w_k · E_idx_k(h) by one grouped matmul kernel a projection pair.

    h [N, D]; idx, weights [N, K]; w_gate, w_up [E, D, F]; w_down
    [E, F, D]. The N·K (token, choice) pairs are sorted by expert
    (stable, so a token's rows keep their order inside a group), each
    expert multiplies its own run of rows, and the rows go back to
    their tokens by the inverse permutation, weighted and summed in
    float32. ``at`` = ``(base, count)``: the matrices are stacks of MORE
    than this layer's experts (every layer's, ``[L * E, ...]``) and expert
    ``i`` of ``count`` is row ``base + i``. The kernels take the stacks
    whole and reach a row through their index maps: a layer's slice of a
    stack would be copied out first, three times the experts' bytes a
    layer (PERF.md, PR 34).

    ``share``: the ``count`` experts are a share of the router's, and an
    ``idx`` outside ``[0, count)`` is a pair this chip does not compute
    (as ``compacted_experts`` reads it). Those pairs sort behind the last
    held expert's run and no run holds them, so the walk visits only the
    blocks the local pairs fill: the kernels neither read nor write the
    other rows, which come back UNINITIALISED. A select on "the pair is
    local" takes them out before the weighted sum; a zero weight would
    not (0 × NaN)."""
    n, k = idx.shape
    base, count = at or (0, w_gate.shape[0])
    tm, ts = _gmm_rows(n * k)
    tiles_m = -(-n * k // tm)
    with tracing.scope(tracing.SCOPE_MOE_ROUTE):
        flat = idx.reshape(-1)
        if share:
            local = (flat >= 0) & (flat < count)
            flat = jnp.where(local, flat, count)  # absent pairs last
        order = jnp.argsort(flat, stable=True)  # sorted pair -> pair
        # An absent pair's key, ``count``, falls off the end and is dropped.
        sizes = jnp.zeros((count,), jnp.int32).at[flat].add(1, mode="drop")
        walk = _gmm_tiles(sizes, tiles_m, tm)
    with tracing.scope(tracing.SCOPE_MOE_DISPATCH):
        # Rows past N·K fill the last tile: no expert's, never read back.
        padded = jnp.pad(order, (0, tiles_m * tm - n * k), mode="edge")
        rows = h[padded // k]  # [tiles · tm, D]
    mid = _gmm(rows, (w_gate, w_up), base, walk, tm, ts, "tk_gmm_gate_up")
    out = _gmm(mid, (w_down,), base, walk, tm, ts, "tk_gmm_down")  # sorted
    with tracing.scope(tracing.SCOPE_MOE_DISPATCH):
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
        out = out[inverse].reshape(n, k, -1).astype(jnp.float32)
        if share:
            out = jnp.where(local.reshape(n, k, 1), out, 0.0)
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
    with tracing.scope(tracing.SCOPE_MOE_ROUTE):
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
        rows = h[tok]
        with tracing.scope(tracing.SCOPE_MOE_EXPERTS):
            y = _swiglu(rows, *(
                lax.dynamic_index_in_dim(m, base + ex, keepdims=False)
                for m in (w_gate, w_up, w_down)
            )).astype(jnp.float32)  # [cap, D]
        y = y * jnp.where(slot < sizes[ex], w_flat[pair], 0.0)[:, None]
        return out.at[tok].add(y)

    with tracing.scope(tracing.SCOPE_MOE_DISPATCH):
        out = lax.fori_loop(
            0, tiles_to[-1], tile, jnp.zeros((n, d), jnp.float32)
        )
        return out.astype(h.dtype)


def all_experts(h, idx, weights, w_gate, w_up, w_down):
    """The same sum with every expert computed for every row and the
    unrouted ones weighted by zero."""
    e = w_gate.shape[0]
    with tracing.scope(tracing.SCOPE_MOE_DISPATCH):
        combine = jnp.sum(
            jax.nn.one_hot(idx, e, dtype=jnp.float32) * weights[..., None],
            axis=1,
        )  # [N, E]
    with tracing.scope(tracing.SCOPE_MOE_EXPERTS):
        gate = jax.nn.silu(jnp.einsum("nd,edf->enf", h, w_gate))
        up = jnp.einsum("nd,edf->enf", h, w_up)
        out = jnp.einsum("enf,efd->end", gate * up, w_down)
    with tracing.scope(tracing.SCOPE_MOE_DISPATCH):
        return jnp.einsum(
            "end,ne->nd", out.astype(jnp.float32), combine
        ).astype(h.dtype)


def _form(pairs: int, count: int, width: int, stacked: bool, d: int, f: int,
          itemsize: int) -> str:
    """The form of the sum of ``pairs`` token-choice pairs drawn over a
    router of ``width`` outputs of which ``count`` meet an expert held
    here (``width`` = ``count``: every expert is), an expert's matrices
    ``[d, f]`` and ``[f, d]`` of ``itemsize`` bytes a number, rows of
    stacks (always so for a share) or the layer's own tensors. The grouped
    form is taken where all three hold, each read from the shapes:

    - a held expert can expect the floor's pairs, ``pairs / width``: the
      floor against the einsum over own tensors, the lower of the two
      floors against the loop out of stacks;
    - the kernels fit: they hold an expert's gate and up matrices twice in
      VMEM (``_gmm_vmem``);
    - the rows sorted and copied for nothing, the ABSENT pairs', stay
      under ``_GROUPED_MAX_ABSENT_ROWS`` of the held experts' weight rows
      (none where every expert is held). A floor of 0 is
      ``chipbench/tick_forms.py``'s way to time the grouped form at every
      row count: it lifts this bound with the floor."""
    floor = _GROUPED_MIN_PAIRS_PER_EXPERT
    if stacked:
        floor = min(floor, _GROUPED_MIN_PAIRS_OUT_OF_STACKS)
    tm, ts = _gmm_rows(pairs)
    absent = pairs * (width - count) // width
    if (
        pairs >= floor * width
        and _gmm_vmem(2, d, f, tm, ts, itemsize) <= _GMM_VMEM_BYTES
        and (floor == 0 or absent <= _GROUPED_MAX_ABSENT_ROWS * count * 3 * f)
    ):
        return "grouped"
    return "compacted" if stacked else "all_experts"


def expert_form(cfg, rows: int) -> str | None:
    """The form ``routed_moe_mlp`` sums ``rows`` tokens' pairs by:
    ``"grouped"``, ``"compacted"``, ``"all_experts"``; None: the config
    has no routed layer. The experts come out of stacks where the model
    hands them on so (``scan_periods``' ``experts_at`` under a
    ``window_pattern``, the double layer's), and a share is reached as
    stacks are."""
    if not cfg.routed_moe:
        return None
    return _form(
        rows * cfg.expert_top_k, cfg.held_experts[1], cfg.router_width,
        stacked=cfg.moe_partial
        or bool(cfg.window_pattern or cfg.linear_pattern)
        or cfg.attn_blocks == 2,
        d=cfg.d_model, f=cfg.moe_d_ff,
        itemsize=jnp.dtype(cfg.dtype).itemsize,
    )


def grouped_form(cfg, rows: int) -> bool:
    """Whether ``routed_moe_mlp`` sums ``rows`` tokens' pairs by
    ``grouped_experts``."""
    return expert_form(cfg, rows) == "grouped"


@tracing.scope(tracing.SCOPE_MOE_ROUTE)
def grouped_counts(routing, count: int, first: int | None = None):
    """What ``grouped_experts`` multiplied for the routing [L, ..., K] of
    L layers' calls over ``count`` experts each: int32 (the pairs, the
    rows of the pieces its kernels multiplied: every piece an expert's
    run touches, whole), summed over the layers. Their quotient is the
    pieces' fill, what uneven routing costs the kernels
    (``ServeMetrics.moe_grouped_rows``, ``_tile_rows``). ``first``: the
    ``count`` experts are a share from that output of the router on, and
    the pairs are the local ones."""
    flat = routing.reshape(routing.shape[0], -1)
    pairs = flat.shape[1]
    _tm, ts = _gmm_rows(pairs)

    def sizes(layer):
        return jnp.sum(
            layer[:, None] == jnp.arange(count), axis=0, dtype=jnp.int32
        )

    def pieces(layer):
        return _gmm_tiles(sizes(layer), -(-pairs // ts), ts)[3]

    if first is None:
        multiplied = jnp.int32(flat.size)
    else:
        flat = flat - first
        multiplied = jax.vmap(sizes)(flat).sum()
    return jnp.stack([multiplied, jax.vmap(pieces)(flat).sum() * ts])


def routed_experts(h, idx, weights, w_gate, w_up, w_down, at=None, width=None):
    """The form the static shapes call for (module docstring). ``at`` =
    ``(base, count)``: the matrices are stacks and this layer's experts
    their rows ``[base, base + count)``. ``width``: the ``count`` experts
    are a SHARE of a router's ``width`` outputs (``at`` is given), ``idx``
    counts from the first held expert and a value outside ``[0, count)``
    is a pair that adds nothing here; default every expert is here."""
    n, k = idx.shape
    count = w_gate.shape[0] if at is None else at[1]
    form = _form(
        n * k, count, width or count, at is not None, *w_gate.shape[-2:],
        w_gate.dtype.itemsize,
    )
    if form == "grouped":
        return grouped_experts(
            h, idx, weights, w_gate, w_up, w_down, at, share=width is not None
        )
    if form == "compacted":
        # Few rows an expert, out of stacks: the compacted form reaches an
        # expert by ONE dynamic index, which fuses into its products. The
        # all-experts einsum over a slice of the stacks has the compiler
        # re-lay the WHOLE stacks once a dispatch (PERF.md, PR 34: 4 GB
        # of temporaries at 64 experts of 2304 x 896 in 8 layers).
        # Twice the pairs a held expert can expect, in whole sublane groups.
        cap = -(-2 * n * k // (width or count) // 16) * 16
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
        n_group=cfg.n_group, topk_group=cfg.topk_group,
    )
    experts = experts or layer.get("experts_at")
    *mats, base = experts or (
        *(layer[n] for n in ("w_gate", "w_up", "w_down")), 0
    )
    mats = [load_weight(m, cfg.dtype) for m in mats]
    first, count = cfg.held_experts
    if cfg.moe_partial:
        out = routed_experts(
            x, idx - first, weights, *mats, at=(base, count),
            width=cfg.router_width,
        )
    else:
        out = routed_experts(
            x, idx, weights, *mats, at=(base, count) if experts else None
        )
    if cfg.zero_experts:
        # The identity experts: w · h, no weights.
        with tracing.scope(tracing.SCOPE_MOE_DISPATCH):
            w_zero = jnp.sum(
                jnp.where(idx >= cfg.n_experts, weights, 0.0), axis=-1
            )
            out = (
                out.astype(jnp.float32)
                + w_zero[:, None] * x.astype(jnp.float32)
            ).astype(x.dtype)
    if cfg.n_shared_experts:
        with tracing.scope(tracing.SCOPE_FFN):
            out = out + _swiglu(x, *(
                load_weight(layer[n], cfg.dtype)
                for n in ("ws_gate", "ws_up", "ws_down")
            ))
    return out.reshape(b, s, d), idx.reshape(b, s, -1)
